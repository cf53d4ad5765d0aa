"""``layer_metrics/ut.exit_fused_sites_share.py``: the share of a looped
model's exits whose gradients the head's forward rule makes, on runs
written down by hand; nothing where the model does not loop or the program
has no such counter (the parent's); its cells are the ones whose
configuration loops, as ``BENCHMARK.json`` lists them.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import glob
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run as harness  # noqa: E402

NAME = "ut.exit_fused_sites_share"
LOOPS = {"model": {"ut_steps": 4, "layer_pattern": "*-" * 6}}
ONCE = {"model": {"layer_pattern": "*-" * 6}}


@pytest.fixture(scope="module")
def reader():
    return harness.load_layer_metrics()[NAME]


def _run(pipeline, config=LOOPS):
    window = {} if pipeline is None else {"pipeline": pipeline}
    return SimpleNamespace(window=window, config=config, cell={}, trace=None)


@pytest.mark.parametrize("exits, fused, share", [
    (4, 4, 100.0), (4, 0, 0.0), (4, 2, 50.0), (3, 3, 100.0),
])
def test_it_reads_the_share_of_the_exits(reader, exits, fused, share):
    pipeline = {"ut_steps": exits, "ut_layer_passes": 12 * exits,
                "ut_exit_heads": exits, "ut_exit_fused_heads": fused}
    assert reader.read(_run(pipeline)) == share


@pytest.mark.parametrize("pipeline, config", [
    (None, LOOPS), ({}, LOOPS),
    # the parent's program: it loops and counts its exits, not this
    ({"ut_steps": 4, "ut_layer_passes": 48, "ut_exit_heads": 4}, LOOPS),
    ({"ut_exit_heads": 0, "ut_exit_fused_heads": 0}, LOOPS),
    ({"ut_exit_heads": 4, "ut_exit_fused_heads": 4}, ONCE),
    ({"ut_exit_heads": 4, "ut_exit_fused_heads": 4},
     {"model": {"ut_steps": 1}}),
    ({"ut_exit_heads": 4, "ut_exit_fused_heads": 4}, {}),
], ids=["no_window", "empty", "no_such_counter", "no_exit", "no_loop",
        "one_pass", "no_model"])
def test_nothing_to_read_is_none_and_raises_nothing(reader, pipeline, config):
    assert reader.read(_run(pipeline, config)) is None


def test_its_cells_are_the_ones_whose_model_loops(reader):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    assert bench["per_layer"][-1]["name"] == NAME  # appended, at the end
    entry = bench["per_layer"][-1]
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "higher",
        "source": "program_counter", "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": entry["workloads"],
    }
    assert reader.UNIT == "%" and reader.LAYER == "step program"
    taken = []
    for path in sorted(glob.glob(os.path.join(BENCH, "cells", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        if reader.CELLS(cell):
            taken.append(os.path.basename(path)[:-5])
    assert entry["workloads"] == taken == ["ouro-2.6b-d6.steady"]
    # the loop's other readers are read in the same cells
    for other in ("ut.layer_passes_per_step", "ut.exit_entropy_nats"):
        (m,) = [m for m in bench["per_layer"] if m["name"] == other]
        assert m["workloads"] == taken
    # a cell of another data directory is left to ``read``
    assert reader.CELLS({"config": "no-such-configuration"})
