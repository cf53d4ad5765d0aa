"""``benchmark/scopes.py`` held to a small ``XSpace`` built here: device
time by the program's scopes (parts, phases, the innermost-known-scope
rule, the sum equal to the busy seconds) and the idle gaps by the host
spans on the profiler's clock (the right span, the clock check).

The built trace, in units of 0.1 ms: five executions of one step program,
one every 1000 for 900, so the stretch is three whole steps from 1000 to
4000; each execution runs the operations of ``OPS`` (a ``while`` with a
nested grouped matmul among them) with four gaps; the train thread's line
holds a ``train`` step a period, each with ``dispatch``, ``device_wait``
and ``stage`` (step 2's with a ``ckpt_stage`` inside), as a loop that
keeps one step in flight lays them.
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import scopes  # noqa: E402
import xplane  # noqa: E402

U = 100_000  # ns a unit
STEP = "jit_train_step(123)"
TOP = "jit(train_step)/"
REMAT = "transpose(jvp(jvp()))/checkpoint/rematted_computation/"
# name, start and length inside an execution (units), tf_op path or None
OPS = (
    ("%fusion.1 = f32[8] fusion(%p)", 10, 100,
     TOP + "jvp(scope/layer/attn)/bhqk,bkhd->bqhd/dot_general"),
    ("%copy.1 = f32[8] copy(%p)", 120, 20, TOP + "jvp()/copy"),
    ("%while.1 = (f32[8]) while(%t)", 150, 200,
     TOP + "jvp(scope/layer/mlp/scope/layer/moe/experts)/while"),
    # the compiler's own grouped matmul and a copy of the loop's state: no
    # scope's path reaches either
    ("%ragged-dot-none.1 = f32[8] custom-call(%a)", 160, 150,
     "ragged-dot-none:"),
    ("%copy.2 = f32[8] copy(%c)", 320, 20, None),
    ("%fusion.2 = f32[8] fusion(%p)", 350, 100,
     TOP + "transpose(jvp(scope/layer/mlp))/mul"),
    ("%flash_attn_fwd.1 = f32[8] custom-call(%q)", 450, 50,
     TOP + REMAT + "scope/layer/attn/scope/layer/attn/window/pallas_call"),
    ("%fusion.3 = f32[8] fusion(%p)", 600, 30,
     TOP + "jvp(scope/layer/ssm/scope/mystery/deeper)/add"),
    # a layout change of the compiler's between two operations of one part
    ("%copy.3 = f32[8]{0} copy(%f)", 630, 10, None),
    ("%fusion.4 = f32[8] fusion(%p)", 640, 60,
     TOP + "transpose(jvp(scope/layer/gdn/scope/layer/gdn/scan))/while"),
    ("%convert.1 = bf16[8] convert(%p)", 700, 20, None),
    ("%fusion.5 = f32[8] fusion(%p)", 720, 80,
     TOP + "scope/optimizer/jit(_where)/select_n"),
    ("%fusion.6 = f32[8] fusion(%p)", 800, 70,
     TOP + "transpose(jvp(scope/xent))/reduce_sum"),
)
BUSY = 740  # units an execution: the while's child lies inside it
# the train thread, inside host step k (units from k * 1000)
HOST = (
    ("train", -850, 990), ("dispatch", -840, 40),
    ("device_wait", -450, 355), ("stage", -90, 220),
)
CKPT_STAGE = ("ckpt_stage", -80, 205)


def _build(pb, host_shift=0, ref_value=False):
    space = pb.XSpace()
    dev = space.planes.add(name="/device:TPU:0")
    dev.stat_metadata[1].name = "tf_op"
    dev.stat_metadata[2].name = "hlo_category"
    dev.event_metadata[1].name = STEP
    for i, (name, _s, _d, path) in enumerate(OPS, start=10):
        meta = dev.event_metadata[i]
        meta.name = name
        meta.stats.add(metadata_id=2, str_value="fusion")
        if path is None:
            continue
        if ref_value:
            # the stat's value as a reference into the stat metadata
            dev.stat_metadata[100 + i].name = path
            meta.stats.add(metadata_id=1, ref_value=100 + i)
        else:
            meta.stats.add(metadata_id=1, str_value=path)
    modules = dev.lines.add(name=xplane.MODULES_LINE, timestamp_ns=7)
    ops = dev.lines.add(name=xplane.OPS_LINE, timestamp_ns=7)
    for k in range(5):
        modules.events.add(
            metadata_id=1, offset_ps=k * 1000 * U * 1000,
            duration_ps=900 * U * 1000,
        )
        for i, (_n, start, dur, _p) in enumerate(OPS, start=10):
            ops.events.add(
                metadata_id=i, offset_ps=(k * 1000 + start) * U * 1000,
                duration_ps=dur * U * 1000,
            )
    host = space.planes.add(name="/host:CPU")
    names = {}
    for name in ("train", "dispatch", "device_wait", "stage", "ckpt_stage",
                 "prefetch"):
        names[name] = len(names) + 1
        host.event_metadata[names[name]].name = name
    other = host.lines.add(name="prefetch/9", timestamp_ns=7)
    other.events.add(metadata_id=names["prefetch"], offset_ps=0,
                     duration_ps=5000 * U * 1000)
    line = host.lines.add(name="main/77", timestamp_ns=7)
    for k in range(1, 6):
        for name, start, dur in HOST + ((CKPT_STAGE,) if k == 2 else ()):
            line.events.add(
                metadata_id=names[name],
                offset_ps=(k * 1000 + start + host_shift) * U * 1000,
                duration_ps=dur * U * 1000,
            )
    return space


@pytest.fixture(scope="module")
def pb():
    return scopes.load_proto()


@pytest.fixture(scope="module")
def table(pb, tmp_path_factory):
    """The built trace through the command line, as a reader starts it."""
    d = tmp_path_factory.mktemp("scopes")
    trace = str(d / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb).SerializeToString())
    assert scopes.main([trace, str(d / "out.json")]) == 0
    with open(d / "out.json") as f:
        return json.load(f)


def _seconds(table, keep):
    return sum(r[3] for r in table["rows"] if keep(*r[:3]))


@pytest.mark.parametrize("path,want", [
    (TOP + "jvp(scope/embed)/gather", ("head", "embed", "fwd")),
    (TOP + "transpose(jvp(scope/lm_head))/btd,dv->btv/dot_general",
     ("head", "lm_head", "bwd")),
    (TOP + "jvp(scope/layer/mlp/scope/layer/moe/route/scope/layer/moe/"
     "route/groups)/top_k", ("moe", "layer/moe/route/groups", "fwd")),
    (TOP + "jvp(scope/layer/mlp)/btd,df->btf/dot_general",
     ("mlp", "layer/mlp", "fwd")),
    (TOP + "jvp(scope/layer/sscan/scope/layer/out_norm)/mul",
     ("mixer", "layer/sscan", "fwd")),
    (TOP + "jvp(scope/layer/attn/scope/layer/attn/gate)/logistic",
     ("attn", "layer/attn/gate", "fwd")),
    (TOP + REMAT + "scope/layer/gmu/scope/layer/gmu/in_proj/dot_general",
     ("mixer", "layer/gmu/in_proj", "recompute")),
    (TOP + "transpose(jvp(jvp()))/checkpoint/scope/layer/attn/mul",
     ("attn", "layer/attn", "bwd")),
    (TOP + "scope/grad_norm/sqrt", ("update", "grad_norm", "fwd")),
    (TOP + "scope/grad_sync/psum", ("update", "grad_sync", "fwd")),
    (TOP + "jvp(scope/layer/mlp/scope/new_thing)/mul",
     ("mlp", "layer/mlp", "fwd")),
    (TOP + "jvp(scope/layer/new_kind/scope/layer/new_kind/scan)/while",
     ("unscoped", "", "fwd")),
    (TOP + "jvp(microscope/embed)/mul", ("unscoped", "", "fwd")),
    ("", ("unscoped", "", "fwd")),
])
def test_a_path_gives_part_scope_and_phase(path, want):
    assert scopes.classify(path)[:3] == want


@pytest.mark.parametrize("path,name,want", [
    ("ragged-dot-none:", "%ragged-dot-none.5",
     ("moe", "layer/moe/experts", "fwd", (), "name")),
    ("state.params['layers'][0]['moe'].w_up:", "%copy.518",
     ("moe", "layer/moe", "fwd", (), "leaf")),
    ("state.opt_state.inner_state[0].nu['embed']['tokens'][1]:", "%copy.7",
     ("head", "embed", "fwd", (), "leaf")),
    ("state.params['layers'][3]['gdn']['in_proj']:", "%copy.8",
     ("mixer", "layer/gdn", "fwd", (), "leaf")),
    ("state.params['layers'][0]['attn_norm']['scale']:", "%copy.9",
     ("unscoped", "", "fwd", (), "")),
    ("", "%copy-done.13", ("unscoped", "", "fwd", (), "")),
    # a scope on the path wins over the name
    (TOP + "transpose(jvp(scope/layer/attn))/mul", "%ragged-dot-none.1",
     ("attn", "layer/attn", "bwd", (), "path")),
])
def test_what_the_compiler_named_itself_is_found_by_name_or_leaf(
        path, name, want):
    assert scopes.classify(path, name) == want


def test_an_unknown_scope_is_named_and_counts_with_what_encloses_it():
    assert scopes.classify(TOP + "jvp(scope/layer/mlp/scope/new_thing)/mul")[
        3] == ("new_thing",)
    assert scopes.classify(
        TOP + "jvp(scope/layer/new_kind/scope/layer/new_kind/scan)/while"
    )[3] == ("layer/new_kind", "layer/new_kind")


def test_every_scope_of_the_table_has_one_part():
    assert set(scopes.KNOWN.values()) == {
        "attn", "mlp", "moe", "mixer", "head", "update", None,
    }
    assert scopes.KNOWN["layer/out_norm"] is None
    assert scopes.KNOWN["layer/moe/experts"] == "moe"


@pytest.mark.parametrize("part,units", [
    ("attn", 150), ("mlp", 100), ("moe", 200), ("mixer", 100),
    ("head", 70), ("update", 80), ("unscoped", 40),
])
def test_parts_hold_their_operations_own_time(table, part, units):
    assert table["parts"][part] == pytest.approx(3 * units * U / 1e9)


@pytest.mark.parametrize("phase,units", [
    ("fwd", 100 + 20 + 200 + 40 + 20 + 80), ("bwd", 100 + 60 + 70),
    ("recompute", 50),
])
def test_phases(table, phase, units):
    got = _seconds(table, lambda _p, _s, ph: ph == phase)
    assert got == pytest.approx(3 * units * U / 1e9)


def test_a_while_counts_its_own_time_and_its_child_its_own(table):
    rows = {tuple(r[:3]): r for r in table["rows"]}
    experts = rows[("moe", "layer/moe/experts", "fwd")]
    # 30 of the while's own, 150 of the grouped matmul inside it (by its
    # name) and 20 of the copy inside it (from the loop around it)
    assert experts[3] == pytest.approx(3 * 200 * U / 1e9)
    assert experts[4] == 9
    assert table["attributed_by"] == pytest.approx({
        "path": 3 * (BUSY - 40 - 150 - 20 - 10) * U / 1e9,
        "name": 3 * 150 * U / 1e9, "parent": 3 * 20 * U / 1e9,
        "between": 3 * 10 * U / 1e9, "unscoped": 3 * 40 * U / 1e9,
    })
    # the copy between a Mamba-2 and a delta-rule operation: their part,
    # no scope of its own, the phase of the one before it
    between = {tuple(r[:3]): r for r in table["rows"]}[("mixer", "", "fwd")]
    assert between[3:] == [pytest.approx(3 * 10 * U / 1e9), 3]


def test_the_sum_is_the_busy_time_of_the_stretch(table, pb, tmp_path):
    assert table["steps"] == 3
    assert table["window_s"] == pytest.approx(3000 * U / 1e9)
    assert table["own_s"] == pytest.approx(3 * BUSY * U / 1e9)
    assert table["busy_s"] == pytest.approx(table["own_s"], rel=1e-12)
    assert sum(table["parts"].values()) == pytest.approx(table["own_s"])
    # and what xplane.py reduces the same file to
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb).SerializeToString())
    reduced = xplane.reduce_planes(xplane.load(trace))
    assert reduced["devices"][0]["busy_s"] == table["busy_s"]
    assert reduced["devices"][0]["steps"] == table["steps"]


def test_unknown_scopes_and_unscoped_operations_are_listed(table):
    assert [r[:2] for r in table["unknown_scopes"]] == [["mystery", "mixer"]]
    assert table["unknown_scopes"][0][2] == pytest.approx(3 * 30 * U / 1e9)
    outside = {r[0]: r for r in table["unscoped_ops"]}
    # by kind of operation, the numbers taken out
    assert set(outside) == {"%copy", "%convert"}
    assert outside["%convert"][1] == ""
    assert outside["%copy"][1] == "jit(train_step)/jvp()/copy"


def test_a_path_given_by_reference_reads_the_same(pb, tmp_path, table):
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb, ref_value=True).SerializeToString())
    assert scopes.reduce_file(trace)["parts"] == table["parts"]


@pytest.mark.parametrize("span,units,gaps", [
    ("device_wait", 140 + 140 + 130, 3), ("step", 300, 3),
    ("stage", 30, 3), ("ckpt_stage", 10, 1), (scopes.NO_SPAN, 30, 3),
])
def test_a_gap_goes_to_the_deepest_span_at_its_start(table, span, units, gaps):
    host = table["host"]
    assert host["line"] == "main/77"
    rows = {r[0]: r for r in host["gaps_by_span"]}
    assert rows[span][1] == pytest.approx(units * U / 1e9)
    assert rows[span][2] == gaps


def test_idle_seconds_by_span_sum_to_the_stretchs_idle_time(table):
    host = table["host"]
    assert host["idle_s"] == pytest.approx(table["idle_s"])
    assert sum(r[1] for r in host["gaps_by_span"]) == pytest.approx(
        table["idle_s"])
    assert table["idle_s"] == pytest.approx((3000 - 3 * BUSY) * U / 1e9)
    assert host["clock_check"]["ok"]
    assert host["clock_check"]["device_wait_end_after_its_execution_ns"] == [
        5 * U] * 3
    assert host["clock_check"]["host_less_device_ns"] == [-840 * U, 5 * U]
    assert (host["host_steps"], host["chunk_steps"]) == (3, 1)


@pytest.mark.parametrize("shift,holds", [
    (0, True), (3, True), (-3, True),
    (300, False),    # the host 30 ms late: order holds, no wait is tight
    (900, False),    # 90 ms late: a wait returns before its execution ends
    (-300, False),   # 30 ms early: paired a step off, no wait is tight
    (-5000, False),  # another epoch
])
def test_the_clock_check_refuses_a_shifted_host_line(pb, tmp_path, shift, holds):
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb, host_shift=shift).SerializeToString())
    host = scopes.reduce_file(trace)["host"]
    assert host["clock_check"]["ok"] is holds
    assert ("refused" in host) is not holds
    assert ("gaps_by_span" in host) is holds


CELL = {"moe": True, "save_memory_interval": 10, "max_steps": 100}
MODEL = {"layer_pattern": "GE*E", "remat": True}


@pytest.mark.parametrize("metric,want", [
    ("step.attn_ms", 150 * U / 1e6), ("step.mlp_ms", 100 * U / 1e6),
    ("step.moe_ms", 200 * U / 1e6), ("step.moe_experts_ms", 200 * U / 1e6),
    ("step.mixer_ms", 100 * U / 1e6), ("step.mixer_scan_ms", 60 * U / 1e6),
    ("step.head_ms", 70 * U / 1e6), ("step.update_ms", 80 * U / 1e6),
    ("step.recompute_ms", 50 * U / 1e6),
    ("step.unscoped_pct", 100.0 * 40 / BUSY),
    ("ckpt.stage_idle_ms_per_chunk_step", 40 * U / 1e6),
    ("loop.idle_unnamed_pct", 100.0 * 330 / 780),
])
def test_a_metric_reads_its_rows_of_the_table(table, metric, want):
    assert scopes.metrics(table, CELL, MODEL)[metric] == pytest.approx(want)


@pytest.mark.parametrize("cell,model,absent", [
    ({"save_memory_interval": 100, "max_steps": 100}, {},
     {"step.moe_ms", "step.moe_experts_ms", "step.mixer_ms",
      "step.mixer_scan_ms", "step.recompute_ms",
      "ckpt.stage_idle_ms_per_chunk_step", "loop.idle_unnamed_pct"}),
    (CELL, {"layer_pattern": "W-WE", "remat": False},
     {"step.mixer_ms", "step.mixer_scan_ms", "step.recompute_ms"}),
])
def test_a_metric_is_read_only_in_the_cells_its_rule_takes(
        table, cell, model, absent):
    got = set(scopes.metrics(table, cell, model))
    assert got == set(scopes.METRICS) - absent


def test_the_part_metrics_sum_to_the_device_time_a_step(table):
    values = scopes.metrics(table, CELL, MODEL)
    parts = sum(
        values[f"step.{p}_ms"]
        for p in ("attn", "mlp", "moe", "mixer", "head", "update")
    )
    unscoped = values["step.unscoped_pct"] / 100.0
    device_ms = 1e3 * table["busy_s"] / table["steps"]
    assert parts + unscoped * device_ms == pytest.approx(device_ms)


def test_a_trace_without_whole_steps_is_refused(pb, tmp_path):
    space = _build(pb)
    del space.planes[0].lines[0].events[3:]
    trace, out = str(tmp_path / "t.xplane.pb"), str(tmp_path / "o.json")
    with open(trace, "wb") as f:
        f.write(space.SerializeToString())
    assert scopes.main([trace, out]) == 3
    with open(out) as f:
        assert "whole step executions" in json.load(f)["refused"]


def test_a_file_with_no_train_thread_gives_the_device_table_alone(pb, tmp_path):
    space = _build(pb)
    del space.planes[1]
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(space.SerializeToString())
    out = scopes.reduce_file(trace)
    assert out["host"] is None
    assert "ckpt.stage_idle_ms_per_chunk_step" not in scopes.metrics(
        out, CELL, MODEL)


def test_run_on_keeps_its_output_beside_the_trace(pb, tmp_path, table):
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb).SerializeToString())
    out = scopes.run_on(trace)
    assert os.path.exists(trace + ".scopes.json")
    assert out["parts"] == table["parts"]
    assert out["took_s"]["whole"] < 60


def _run_of(trace):
    from types import SimpleNamespace

    return SimpleNamespace(
        window={"trace": {"files": [trace]}}, cell=CELL, config={"model": MODEL},
    )


def test_the_first_reader_makes_the_table_and_the_others_find_it(
        pb, tmp_path, capsys):
    trace = str(tmp_path / "t.xplane.pb")
    with open(trace, "wb") as f:
        f.write(_build(pb).SerializeToString())
    run = _run_of(trace)
    assert scopes.read(run, "step.attn_ms") == pytest.approx(150 * U / 1e6)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["device_time_by_scope"]["steps"] == 3
    assert "spans" not in line["device_time_by_scope"]["host"]
    os.remove(trace)
    assert scopes.read(run, "step.head_ms") == pytest.approx(70 * U / 1e6)
    assert capsys.readouterr().out == ""


def test_a_reader_finds_nothing_where_the_trace_file_is_gone(tmp_path):
    """What ``run.py`` hands a reader today: it removes the run's trace
    directory before it asks the readers (PERF.md, Open questions)."""
    run = _run_of(str(tmp_path / "gone.xplane.pb"))
    assert scopes.read(run, "step.attn_ms") is None
    run.window = {}
    assert scopes.read(run, "step.attn_ms") is None


@pytest.mark.parametrize("metric,cells", [
    ("step.attn_ms", 9), ("step.mlp_ms", 9), ("step.moe_ms", 5),
    ("step.moe_experts_ms", 5), ("step.mixer_ms", 4),
    ("step.mixer_scan_ms", 4), ("step.head_ms", 9), ("step.update_ms", 9),
    ("step.recompute_ms", 3), ("step.unscoped_pct", 9),
    ("ckpt.stage_idle_ms_per_chunk_step", 1), ("loop.idle_unnamed_pct", 1),
])
def test_a_rule_takes_its_cells_of_the_benchmarks_nine(metric, cells):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [w["name"] for w in json.load(f)["workloads"]][:9]
    taken = []
    for name in names:
        with open(os.path.join(ROOT, "benchmark", "cells", f"{name}.json")) as f:
            if scopes.takes(metric, json.load(f)):
                taken.append(name)
    assert len(taken) == cells
    if cells == 1:
        assert taken == ["gpt2-124m.save-kill-resume"]
    # a cell of another data directory is left to the reader
    assert scopes.takes(metric, {"config": "no-such-configuration"})
