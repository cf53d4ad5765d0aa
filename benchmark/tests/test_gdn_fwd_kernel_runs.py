"""``layer_metrics/gdn.fwd_kernel_runs_per_step.py``: the delta rule's
forward kernels of a reduced trace counted a step, on rows written down by
hand and on a built device plane; nothing without a trace, such a kernel or
such a layer; its cells are the ones whose configuration names a Gated
DeltaNet layer, as ``BENCHMARK.json`` lists them."""

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

NAME = "gdn.fwd_kernel_runs_per_step"
CALL = "custom_call_target=tpu_custom_call hlo=bf16[1,8192,4096] custom-call("
DELTA_RULE = {"model": {"layer_pattern": "GEGE*E"}}


@pytest.fixture(scope="module")
def reader():
    return harness.load_layer_metrics()[NAME]


def _run(trace, config=DELTA_RULE):
    return SimpleNamespace(trace=trace, config=config, cell={})


def _rows(per_step, steps=19):
    """A reduced trace of ``steps`` whole steps whose every step ran each
    of ``per_step``'s names once."""
    rows = [
        {"name": name, "count": steps, "total_s": 1e-3 * steps,
         "self_s": 1e-3 * steps, "about": CALL}
        for name in per_step
    ]
    return {"steps": steps, "devices": [{"ops": rows, "steps": steps}]}


# a delta-rule layer's forward kernels a step: two where nothing is made
# again (no ``remat``, or a recomputed layer that keeps what the pass read
# and returned), four where the backward pass runs them a second time
@pytest.mark.parametrize("kind", ["chunk", "channel"])
@pytest.mark.parametrize("layers, a_layer", [(3, 2), (6, 2), (6, 4), (1, 4)])
def test_it_counts_the_forward_kernels_of_either_kind(
    reader, kind, layers, a_layer, capsys
):
    forward = [
        f"%gdn_{kind}_{part}_fwd.{i}"
        for i in range(layers * a_layer // 2) for part in ("wy", "read")
    ]
    beside = [f"%gdn_{kind}_wy_bwd.{i}" for i in range(layers)] + [
        f"%gdn_{kind}_read_bwd.{i}" for i in range(layers)
    ] + ["%flash_attn_fwd.2", "%conv_silu_fwd.4", "%fusion.7", "%while.3"]
    trace = _rows(forward + beside)
    # a fusion that reads a kernel's result names it in its text
    trace["devices"][0]["ops"].append({
        "name": "%fusion.9", "count": 19, "total_s": 1.0, "self_s": 1.0,
        "about": f"hlo=bf16[8,8] fusion(bf16[8,8] %gdn_{kind}_read_fwd.1)",
    })
    assert reader.read(_run(trace)) == layers * a_layer
    (line,) = harness.json_lines(capsys.readouterr().out)
    assert line["steps_traced"] == 19
    found = line["forward_delta_rule_kernels"]
    assert found["count"] == 19 * layers * a_layer
    assert sorted(found["names"]) == sorted(forward)


def test_it_reads_a_built_device_plane(reader):
    call = {"custom_call_target": "tpu_custom_call"}
    runs = planes._steps(21, period=1e6, dur=0.9e6)
    plane = planes._device(runs)
    per_step = [
        ("%gdn_channel_wy_fwd.3", 1e5, 1e5, call),
        ("%gdn_channel_read_fwd.4", 3e5, 1e5, call),
        ("%gdn_channel_read_bwd.1", 5e5, 1e5, call),
        ("%gdn_channel_wy_bwd.1", 6e5, 1e5, call),
        ("%flash_attn_fwd.1", 7e5, 1e5, call),
    ]
    plane["lines"][2]["events"] = [
        (name, start + at, dur, stats)
        for _p, start, _d in runs for name, at, dur, stats in per_step
    ]
    trace = xplane.reduce_planes([plane])
    assert reader.read(_run(trace)) == 2.0


@pytest.mark.parametrize("trace, config", [
    (None, DELTA_RULE), ({}, DELTA_RULE), ({"devices": []}, DELTA_RULE),
    (_rows(["%flash_attn_fwd.1", "%gdn_chunk_wy_bwd.1"]), DELTA_RULE),
    (_rows(["%gdn_chunk_wy_fwd.1"], steps=0), DELTA_RULE),
    (_rows(["%gdn_chunk_wy_fwd.1"]), {"model": {"layer_pattern": "M-*E"}}),
    (_rows(["%gdn_chunk_wy_fwd.1"]), {}),
], ids=["untraced", "empty", "no_device", "no_forward_kernel", "no_step",
        "no_such_layer", "no_model"])
def test_nothing_to_read_is_none_and_no_line(reader, trace, config, capsys):
    assert reader.read(_run(trace, config)) is None
    assert capsys.readouterr().out == ""


def test_its_cells_are_the_ones_with_a_delta_rule_layer(reader):
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
        if reader.CELLS(cell):
            taken.append(os.path.basename(path)[:-5])
    assert sorted(entry["workloads"]) == taken == [
        "ling-3.0-flash-d7.steady", "qwen3-next-80b-a3b-d4.steady",
    ]
    # the serial pass's steps are read in the same cells
    (steps,) = [
        m for m in bench["per_layer"] if m["name"] == "gdn.serial_chunk_steps"
    ]
    assert sorted(steps["workloads"]) == taken
    # a cell of another data directory is left to ``read``
    assert reader.CELLS({"config": "no-such-configuration"})
