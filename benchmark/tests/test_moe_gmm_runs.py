"""``layer_metrics/moe.gmm_runs_per_step.py``: the grouped matmuls of a
reduced trace counted a step, on rows written down by hand and on a built
device plane; nothing without a trace or a grouped matmul; its cells are the
ones whose cell file says ``moe``, as ``BENCHMARK.json`` lists them."""

import glob
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import run as harness  # noqa: E402
import test_trace_window as planes  # noqa: E402
import xplane  # noqa: E402

NAME = "moe.gmm_runs_per_step"
CALL = "custom_call_target=tpu_custom_call hlo=bf16[6144,1856] custom-call("


@pytest.fixture(scope="module")
def reader():
    return harness.load_layer_metrics()[NAME]


def _run(trace):
    return SimpleNamespace(trace=trace, config={"model": {}}, cell={})


def _rows(per_step, steps=19):
    """A reduced trace of ``steps`` whole steps whose every step ran each
    of ``per_step``'s names once."""
    rows = [
        {"name": name, "count": steps, "total_s": 1e-3 * steps,
         "self_s": 1e-3 * steps, "about": CALL}
        for name in per_step
    ]
    return {"steps": steps, "devices": [{"ops": rows, "steps": steps}]}


# an expert layer's grouped matmuls a step: ungated 2 forward + 4 backward,
# gated 3 + 6; a share that makes its round again runs the forward ones
# twice, and under ``remat`` a third time
@pytest.mark.parametrize("layers, a_layer", [
    (4, 6), (4, 8), (4, 9), (4, 12), (6, 12), (4, 15), (2, 9),
])
def test_it_counts_the_matmuls_and_not_what_tiles_their_groups(
    reader, layers, a_layer, capsys
):
    matmuls = [f"%ragged-dot-none.{i}" for i in range(layers * a_layer)]
    beside = [f"%ragged-dot-metadata.{i}" for i in range(layers * 3)] + [
        "%flash_attn_fwd.2", "%fusion.7", "%ragged-dot.1",
    ]
    trace = _rows(matmuls + beside)
    # a fusion that reads a grouped matmul's result names it in its text
    trace["devices"][0]["ops"].append({
        "name": "%fusion.9", "count": 19, "total_s": 1.0, "self_s": 1.0,
        "about": "hlo=bf16[8,8] fusion(bf16[8,8] %ragged-dot-none.3)",
    })
    assert reader.read(_run(trace)) == layers * a_layer
    (line,) = harness.json_lines(capsys.readouterr().out)
    assert line["steps_traced"] == 19
    assert line["grouped_matmul_runs"]["count"] == 19 * layers * a_layer
    assert line["grouped_matmul_runs"]["names"] == layers * a_layer


def test_it_reads_a_built_device_plane(reader):
    call = {"custom_call_target": "tpu_custom_call"}
    runs = planes._steps(21, period=1e6, dur=0.9e6)
    plane = planes._device(runs)
    per_step = [
        ("%ragged-dot-none.3", 1e5, 1e5, call),
        ("%ragged-dot-none.4", 3e5, 1e5, call),
        ("%ragged-dot-metadata.1", 5e5, 1e3, call),
        ("%flash_attn_fwd.1", 6e5, 1e5, call),
    ]
    plane["lines"][2]["events"] = [
        (name, start + at, dur, stats)
        for _p, start, _d in runs for name, at, dur, stats in per_step
    ]
    trace = xplane.reduce_planes([plane])
    assert reader.read(_run(trace)) == 2.0


@pytest.mark.parametrize("trace", [
    None, {}, {"devices": []},
    _rows(["%flash_attn_fwd.1", "%ragged-dot-metadata.1"]),
    _rows(["%ragged-dot-none.1"], steps=0),
], ids=["untraced", "empty", "no_device", "no_grouped_matmul", "no_step"])
def test_nothing_to_read_is_none_and_no_line(reader, trace, capsys):
    assert reader.read(_run(trace)) is None
    assert capsys.readouterr().out == ""


def test_its_cells_are_the_ones_that_say_moe(reader):
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert entry == {
        "name": NAME, "unit": reader.UNIT, "better": "lower",
        "source": "device_trace", "layer": reader.LAYER,
        "moves": reader.MOVES, "workloads": entry["workloads"],
    }
    taken = []
    for path in sorted(glob.glob(os.path.join(BENCH, "cells", "*.json"))):
        with open(path) as f:
            cell = json.load(f)
        assert reader.CELLS(cell) == bool(cell.get("moe"))
        if reader.CELLS(cell):
            taken.append(os.path.basename(path)[:-5])
    assert sorted(entry["workloads"]) == taken and len(taken) == 5
    assert not reader.CELLS({}) and reader.CELLS({"moe": True})
