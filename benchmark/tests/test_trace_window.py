"""The traced stretch (``xplane.step_stretch``) and what is read from it,
on hand-built planes in the shape ``xplane.load`` returns, on one recorded
stretch of a real run (``data/``), and the check ``run.py`` makes of its own
last line. Run by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider
"""

import gzip
import importlib.util
import json
import os
import random
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import peaks  # noqa: E402
import run as harness  # noqa: E402
import xplane  # noqa: E402

STEP, TWIN, COPY = "jit_train_step(1)", "jit_train_step(2)", "jit_copy(3)"


def _device(executions, extra_ops=(), name="/device:TPU:0"):
    """A device plane from ``(program, start_ns, dur_ns)`` executions: each
    holds one operation named after it over its middle 80 % (so a tenth of
    every execution is idle at either end), and ``extra_ops`` are added as
    they are."""
    ops = [
        (f"%op_of_{prog}", start + 0.1 * dur, 0.8 * dur, {})
        for prog, start, dur in executions
    ] + list(extra_ops)
    return {"name": name, "lines": [
        {"name": "Steps", "events": []},
        {"name": xplane.MODULES_LINE,
         "events": [(p, s, d, {}) for p, s, d in executions]},
        {"name": xplane.OPS_LINE, "events": ops},
    ]}


def _steps(n, period=1000.0, dur=900.0, t0=0.0, prog=STEP):
    return [(prog, t0 + i * period, dur) for i in range(n)]


def _reduce(*planes):
    return xplane.reduce_planes(list(planes))


# (a) what lies before the first whole step and after the last is left out
def test_operations_outside_the_stretch_are_left_out():
    runs = _steps(6)  # executions at 0 .. 5000; whole: 1000 .. 4000
    extra = [
        ("%before", 200.0, 50.0, {}),    # inside the first, cut, execution
        ("%after", 5500.0, 300.0, {}),   # inside the last
        ("%straddles", 4950.0, 100.0, {}),  # 50 ns of it inside
    ]
    d = _reduce(_device(runs, extra))["devices"][0]
    assert d["steps"] == 4
    assert d["window_s"] == pytest.approx(4000e-9)
    # four executions busy 720 each, the straddler's 50
    assert d["busy_s"] == pytest.approx((4 * 720 + 50) * 1e-9)
    names = {o["name"]: o for o in d["ops"]}
    assert "%before" not in names and "%after" not in names
    assert names["%straddles"]["total_s"] == pytest.approx(50e-9)
    assert names[f"%op_of_{STEP}"]["count"] == 4
    # the gaps: 90 before each op (4), 190 after each of the first three
    # ops' ends to the next op... summed by the operation that ended them
    gaps = {g["before"]: g for g in d["gaps"]}
    assert "%before" not in gaps and "%after" not in gaps
    assert sum(g["seconds"] for g in d["gaps"]) == pytest.approx(
        d["window_s"] - d["busy_s"]
    )
    assert d["whole_file"]["events"] == 9 and d["events"] == 5
    assert d["whole_file"]["busy_s"] > d["busy_s"]


# (b) whatever the trace, 0 < busy_s <= window_s over >= 3 steps, or no number
@pytest.mark.parametrize("seed", range(40))
def test_busy_never_passes_the_window(seed):
    rng = random.Random(seed)
    period = rng.uniform(50.0, 5e8)
    runs, t = [], rng.uniform(0.0, 1e12)
    for _ in range(rng.randint(0, 12)):
        prog = rng.choice([STEP, TWIN, COPY])
        dur = period * (
            rng.uniform(0.001, 0.1) if prog == COPY
            else rng.uniform(0.3, 1.0)
        )
        runs.append((prog, t, dur))
        t += dur + rng.choice([0.0, rng.uniform(0.0, 0.5 * period)])
    ops = [
        (f"%op.{rng.randint(0, 9)}", rng.uniform(-period, t + period),
         rng.choice([0.0, rng.uniform(0.0, 3 * period)]), {})
        for _ in range(rng.randint(0, 200))
    ]
    plane = _device(runs, ops)
    if rng.random() < 0.3:
        plane["lines"][2]["events"] = ops  # no operation of the steps' own
    try:
        r = _reduce(plane)
    except xplane.NoStretch as e:
        assert "/device:TPU:0" in str(e)
        return
    for d in r["devices"] or [{"busy_s": 1, "window_s": 1, "steps": 3}]:
        assert 0 < d["busy_s"] <= d["window_s"]
        assert d["steps"] >= xplane.MIN_STEPS
        assert all(
            o["total_s"] <= o["count"] * d["window_s"] for o in d["ops"]
        )
        idle = sum(g["seconds"] for g in d["gaps"])
        assert idle <= (d["window_s"] - d["busy_s"]) * (1 + 1e-9) + 1e-18
    if r["devices"]:
        assert 0 < r["busy_s"] <= r["window_s"] and r["steps"] >= 3


def test_a_device_busy_all_through_reads_busy_equal_to_window():
    runs = _steps(7)
    d = _reduce(_device(runs, [("%all", -50.0, 1e5, {})]))["devices"][0]
    assert d["busy_s"] == d["window_s"] == 5000e-9 and d["gaps"] == []


# (c) the donating step and the safe twin alternate, short programs between
def test_two_long_programs_are_both_steps_and_short_ones_are_not():
    runs = []
    for i in range(8):
        runs.append((STEP if i % 2 else TWIN, 1000.0 * i, 700.0 + 40 * i))
        runs.append((COPY, 1000.0 * i + 950.0, 20.0))
        runs.append(("jit_convert(4)", 1000.0 * i + 975.0, 5.0))
    d = _reduce(_device(runs))["devices"][0]
    assert d["step_programs"] == sorted([STEP, TWIN])
    assert d["step_executions"] == 8 and d["other_executions"] == 16
    assert d["steps"] == 6
    assert d["window_s"] == pytest.approx(6000e-9)
    mods = {m["name"]: m["count"] for m in d["modules"]}
    assert mods == {STEP: 3, TWIN: 3, COPY: 6, "jit_convert(4)": 6}


# (d) four chips: the means over the planes
def test_four_device_planes_give_means():
    planes = [
        _device(_steps(6 + k, period=1000.0 + 100 * k, dur=900.0 + 90 * k),
                name=f"/device:TPU:{k}")
        for k in range(4)
    ]
    idle = {"name": "/device:TPU:4", "lines": [
        {"name": xplane.OPS_LINE, "events": []}]}
    r = _reduce({"name": "/host:CPU", "lines": []}, *planes, idle)
    assert [d["plane"] for d in r["devices"]] == [
        f"/device:TPU:{k}" for k in range(4)
    ]
    assert [d["steps"] for d in r["devices"]] == [4, 5, 6, 7]
    assert r["steps"] == 5.5
    for key in ("window_s", "busy_s"):
        assert r[key] == pytest.approx(
            sum(d[key] for d in r["devices"]) / 4
        )
    assert r["busy_s"] == pytest.approx(0.8 * 0.9 * r["window_s"])


# (e) executions cut by the capture's start and stop do not count
@pytest.mark.parametrize("first_cut,last_cut", [
    (0.95, 0.95), (0.4, 0.95), (0.95, 0.05), (0.3, 0.3), (0.0, 0.0),
])
def test_cut_executions_at_the_edges_do_not_count(first_cut, last_cut):
    whole = _steps(5, t0=1000.0)
    runs = list(whole)
    if first_cut:  # the capture began inside the execution before
        runs.insert(0, (STEP, 900.0 - 900.0 * first_cut,
                        900.0 * first_cut))
    if last_cut:  # and stopped inside the execution after
        runs.append((STEP, 6000.0, 900.0 * last_cut))
    d = _reduce(_device(runs))["devices"][0]
    # a cut execution of the step program is one of its executions however
    # short it came out: the whole ones between the two edges count
    want = len(runs) - 2
    assert d["steps"] == want
    assert d["window_s"] == pytest.approx(1000e-9 * want)
    assert 1e9 * d["busy_s"] / d["steps"] == pytest.approx(720.0)


# (f) fewer than three whole steps, or no step program: no number
@pytest.mark.parametrize("runs,ops", [
    ([], [("%op", 0.0, 10.0, {})]),
    (_steps(1), None), (_steps(2), None), (_steps(4), None),
    ([(COPY, 0.0, 0.0)] * 6, [("%op", 0.0, 10.0, {})]),
    (_steps(6), [("%elsewhere", 9000.0, 10.0, {})]),
])
def test_fewer_than_three_whole_steps_is_refused(runs, ops):
    plane = _device(runs)
    if ops is not None:
        plane["lines"][2]["events"] = ops
    with pytest.raises(xplane.NoStretch) as e:
        _reduce(plane)
    assert "/device:TPU:0" in str(e.value)
    assert _reduce(_device(_steps(5)))["steps"] == 3


def _write_trace(path, executions):
    """An ``.xplane.pb`` of one TPU plane, through the profiler's own text
    form of the proto (picoseconds from the line's timestamp)."""
    from jax.profiler import ProfileData

    meta = {STEP: 1, "%op": 2}
    mods = "".join(
        f"events {{ metadata_id: 1 offset_ps: {int(1e3 * s)} "
        f"duration_ps: {int(1e3 * d)} }} " for _p, s, d in executions
    )
    ops = "".join(
        f"events {{ metadata_id: 2 offset_ps: {int(1e3 * (s + 0.1 * d))} "
        f"duration_ps: {int(800 * d)} }} " for _p, s, d in executions
    )
    names = "".join(
        f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }} '
        for n, i in meta.items()
    )
    text = (
        'planes { name: "/device:TPU:0" '
        f'lines {{ name: "{xplane.MODULES_LINE}" {mods} }} '
        f'lines {{ name: "{xplane.OPS_LINE}" {ops} }} {names} }}'
    )
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


@pytest.mark.parametrize("n,rc", [(7, 0), (4, 3)])
def test_the_reducer_as_run_py_starts_it(tmp_path, n, rc):
    """``xplane.py <trace> <out>`` on a written trace file: a stretch, or
    rc 3 and a note that ``run.py`` turns into ``Refused``."""
    _write_trace(str(tmp_path / "t.xplane.pb"), _steps(n))
    out = str(tmp_path / "trace_reduced.json")
    assert xplane.main([str(tmp_path / "t.xplane.pb"), out]) == rc
    with open(out) as f:
        reduced = json.load(f)
    if rc:
        assert "2 whole step executions" in reduced["refused"]
        with pytest.raises(harness.Refused, match="2 whole step"):
            harness.reduce_trace(
                [str(tmp_path / "t.xplane.pb")], str(tmp_path)
            )
    else:
        assert reduced["steps"] == n - 2
        assert reduced["window_s"] == pytest.approx((n - 2) * 1e-6)
        assert reduced["busy_s"] == pytest.approx((n - 2) * 0.72e-6)
        got = harness.reduce_trace(
            [str(tmp_path / "t.xplane.pb")], str(tmp_path)
        )
        assert got["steps"] == n - 2


# (g) the readers count the trace's steps, not the host's hooks
def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location("reader_" + name[:4], path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _run_of(trace, config, batch, seq, hooks=20):
    return SimpleNamespace(
        trace=trace, config=config, hook=harness.load_hook("x", config),
        peak=peaks.peaks("TPU v5 lite"),
        cell={"batch": batch, "seq": seq, "moe": True},
        window={"trace": {"step_begin": 100, "step_end": 100 + hooks,
                          "t_begin": 0.0, "t_end": 1.0}},
    )


def test_the_readers_divide_by_the_traces_whole_steps(capsys):
    import flops
    import flops_moe

    call = {"custom_call_target": "tpu_custom_call"}
    period = 1e6
    runs = _steps(21, period=period, dur=0.9 * period)
    ops = []
    for _p, start, _d in runs:
        ops += [
            ("%flash_attn_fused_fwd.1", start + 1e5, 1e5, call),
            ("%flash_attn_bwd_dkv.2", start + 2e5, 2e5, call),
            ("%ragged-dot-none.3", start + 4e5, 3e5, call),
            # a fusion that takes the kernel's result names it in its HLO
            ("%fusion.9", start + 7e5, 1e5,
             {"hlo": "f32[8] fusion(%flash_attn_fused_fwd.1)"}),
        ]
    plane = _device(runs)
    plane["lines"][2]["events"] = ops
    trace = _reduce(plane)
    assert trace["steps"] == 19  # under 20 hooks
    config = _config("olmoe-1b-7b-d2")
    run = _run_of(trace, config, batch=2, seq=4096)
    m = config["model"]

    assert _reader("step.device_ms").read(run) == pytest.approx(
        1e3 * 19 * 7e5 * 1e-9 / 19
    )
    attn = _reader("kernel.attn_roofline").read(run)
    work = flops.attention_kernel_work(
        2, m["num_heads"], 4096, m["model_dim"] // m["num_heads"]
    )
    roof = flops.roofline_seconds(
        {k: v * m["num_layers"] * 19 for k, v in work.items()}, run.peak
    )["seconds"]
    assert attn == pytest.approx(100 * roof / (19 * 3e5 * 1e-9))
    gmm = _reader("kernel.moe_gmm_roofline").read(run)
    work = flops_moe.grouped_matmul_work(m, 2 * 4096)
    roof = flops.roofline_seconds(
        {k: v * m["num_layers"] * 19 for k, v in work.items()}, run.peak
    )["seconds"]
    assert gmm == pytest.approx(100 * roof / (19 * 3e5 * 1e-9))
    lines = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert [ln["steps_traced"] for ln in lines] == [19, 19]
    # attention alone, and the grouped matmuls as the other custom calls;
    # the moe reader prints the same attention seconds from its side
    assert lines[0]["attention_kernels"]["count"] == 2 * 19
    assert lines[0]["other_custom_call_seconds"] == pytest.approx(
        lines[1]["grouped_matmul_kernels"]["seconds"]
    )
    assert lines[1]["other_custom_call_seconds"] == pytest.approx(
        lines[0]["attention_kernels"]["seconds"]
    )


def test_a_dense_cell_reads_every_custom_call_as_before():
    """Where flash attention's are the only ``tpu_custom_call``s (the
    GPT-2 cells), the name filter takes what the target alone took."""
    call = {"custom_call_target": "tpu_custom_call"}
    runs = _steps(8)
    plane = _device(runs)
    plane["lines"][2]["events"] = [
        (f"%flash_attn_fused_{d}.{i}", s + 100 * i, 90.0, call)
        for _p, s, _d in runs for i, d in enumerate(("fwd", "bwd"))
    ]
    trace = _reduce(plane)
    d = trace["devices"][0]
    every = xplane.kernel_seconds(d, ("tpu_custom_call",))
    assert every["count"] == 12 and every["seconds"] == pytest.approx(
        12 * 90e-9
    )
    run = _run_of(trace, _config("gpt2-124m"), batch=16, seq=1024)
    m = run.config["model"]
    import flops

    work = flops.attention_kernel_work(16, m["num_heads"], 1024, 64)
    roof = flops.roofline_seconds(
        {k: v * m["num_layers"] * 6 for k, v in work.items()}, run.peak
    )["seconds"]
    assert _reader("kernel.attn_roofline").read(run) == pytest.approx(
        100 * roof / every["seconds"]
    )
    assert _reader("kernel.moe_gmm_roofline").read(run) is None


# -- one recorded stretch of a real run --------------------------------------
def _recorded():
    sys.path.insert(0, HERE)
    import record_stretch

    with gzip.open(
        os.path.join(HERE, "data", "olmoe-1b-7b-d2.steady.stretch.json.gz"),
        "rt",
    ) as f:
        rec = json.load(f)
    return rec, record_stretch.recorded_plane(rec)


def test_the_recorded_stretch_reads_whole_steps_on_one_clock():
    rec, plane = _recorded()
    host = rec["host"]
    old_window = host["t_end"] - host["t_begin"]
    r = _reduce(plane)
    d = r["devices"][0]
    # what the reduction before PR 33 printed from this very run: the
    # whole file's busy time over the host's window, a malformed line
    assert d["whole_file"]["busy_s"] > old_window
    assert harness.line_breaches(
        _line(busy_s=d["whole_file"]["busy_s"], window_s=old_window), True
    )
    assert d["whole_file"]["busy_s"] == rec["old_line"]["busy_s"]
    # and the same trace on one clock
    assert 0 < r["busy_s"] <= r["window_s"]
    assert harness.line_breaches(
        _line(busy_s=r["busy_s"], window_s=r["window_s"]), True
    ) == []
    assert {k: r[k] for k in rec["expect"]} == rec["expect"]
    assert r["steps"] == 19 and host["hooks"] == 20
    assert len(d["step_programs"]) == 1
    assert d["step_programs"][0].startswith("jit_train_step(")
    assert d["step_executions"] == 21
    # the device's step against the hooks' median period over the same
    # steps, and against the whole window's (which drifts by 0.3 %)
    device_ms = 1e3 * r["busy_s"] / r["steps"]
    assert device_ms == pytest.approx(
        rec["step_p50_ms_over_the_hooks"], rel=3e-3
    )
    assert device_ms == pytest.approx(rec["loop.step_p50_ms"], rel=5e-3)
    # the old reading divided the whole file by the 20 hooks: 0.98 % low
    old_ms = 1e3 * d["whole_file"]["busy_s"] / host["hooks"]
    assert 0.985 * device_ms < old_ms < 0.991 * device_ms
    assert d["idle_s"] == pytest.approx(r["window_s"] - r["busy_s"], abs=1e-9)
    assert sum(g["seconds"] for g in d["gaps"]) <= d["idle_s"]
    assert d["idle_s"] / r["window_s"] < 1e-3


# -- run.py checks its own last line -----------------------------------------
def _line(**device):
    return {
        "correct": True, "attempted": 199, "failed": 0,
        "metrics": {"step.device_ms": {"value": 226.0, "unit": "ms"}},
        "device": dict(
            {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
             "memory_peak_bytes": 13262491648}, **device
        ),
        "breakdown": {"device_ops": [["%fusion.96", 0.28]] * 10,
                      "idle_gaps": [["%fusion.477", 0.0005]]},
    }


def test_a_sound_line_has_no_breach():
    assert harness.line_breaches(_line(busy_s=4.29, window_s=4.2905), True) \
        == []
    assert harness.line_breaches(_line(busy_s=4.29, window_s=4.29), True) \
        == []
    untraced = _line()
    del untraced["breakdown"]
    assert harness.line_breaches(untraced, False) == []


@pytest.mark.parametrize("spoil,word", [
    (lambda r: r["device"].update(busy_s=4.4623, window_s=4.4620), "busy_s"),
    (lambda r: r["device"].update(busy_s=0.0), "busy_s"),
    (lambda r: r["device"].pop("window_s"), "busy_s"),
    (lambda r: r["device"].update(busy_s=float("nan")), "busy_s"),
    (lambda r: r.update(correct="true"), "correct"),
    (lambda r: r.update(attempted=199.0), "attempted"),
    (lambda r: r.pop("failed"), "failed"),
    (lambda r: r.update(metrics={}), "metrics"),
    (lambda r: r["metrics"].update(x={"value": float("inf"), "unit": "ms"}),
     "metric x"),
    (lambda r: r["metrics"].update(x={"value": 1.0}), "metric x"),
    (lambda r: r["device"].update(memory_peak_bytes=0), "memory_peak_bytes"),
    (lambda r: r["device"].update(count=True), "count"),
    (lambda r: r["device"].pop("kind"), "kind"),
    (lambda r: r.update(device=None), "device"),
    (lambda r: r["breakdown"]["device_ops"].append(["%x", 1.0]),
     "device_ops"),
    (lambda r: r["breakdown"].update(idle_gaps=[["%x", "1"]]), "idle_gaps"),
])
def test_a_breach_of_the_lines_contract_is_named(spoil, word):
    result = _line(busy_s=4.29, window_s=4.2905)
    spoil(result)
    breaches = harness.line_breaches(result, True)
    assert breaches and any(word in b for b in breaches)


def test_main_refuses_a_line_it_would_print_malformed(monkeypatch, capsys):
    bad = _line(busy_s=4.4623, window_s=4.4620)
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: bad)
    rc = harness.main(
        ["--workload", "x", "--seed", "1", "--seconds", "1", "--trace", "1"]
    )
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 3 and len(out) == 1
    last = json.loads(out[-1])
    assert "correct" not in last and "busy_s" in last["breaches"][0]
    good = _line(busy_s=4.29, window_s=4.2905)
    monkeypatch.setattr(harness, "run_cell", lambda *a, **k: good)
    assert harness.main(
        ["--workload", "x", "--seed", "1", "--seconds", "1", "--trace", "1"]
    ) == 0
    assert json.loads(capsys.readouterr().out.splitlines()[-1]) == good


# -- the traced path of run.py, end to end on the CPU ------------------------
def test_cpu_rehearsal_of_a_traced_cell(monkeypatch, capsys):
    """A toy cell through launcher, agent and worker with the profiler on.
    A CPU trace has no device plane, so the reduction of a hand-built one
    stands in for it: the lines ``run.py`` prints from the stretch, the
    readers and the result's ``device`` are the real code."""
    seen = {}

    def reduced_of_a_built_plane(files, run_dir):
        seen["files"] = list(files)
        return _reduce(_device(_steps(21, period=1e6, dur=9e5)))

    monkeypatch.setattr(harness, "reduce_trace", reduced_of_a_built_plane)
    res = harness.run_cell(
        "toy.steady", seed=3000000017, seconds=3.0, trace=True,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert seen["files"] and seen["files"][0].endswith(".xplane.pb")
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["busy_s"] == pytest.approx(19 * 0.72e-3)
    assert res["device"]["window_s"] == pytest.approx(19e-3)
    assert res["metrics"]["step.device_ms"]["value"] == pytest.approx(0.72)
    assert "kernel.attn_roofline" not in res["metrics"]  # no peak: the CPU
    res["device"]["memory_peak_bytes"] = 1  # the CPU backend reports none
    assert harness.line_breaches(res, True) == []
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    line = next(n for n in notes if "traced_stretch" in n)
    assert line["traced_stretch"]["steps"] == 19
    assert line["traced_stretch"]["idle_s"] == pytest.approx(0.28 * 19e-3)
    assert line["whole_trace_file"]["events"] == 21
    assert line["host_clock"]["hooks"] >= 1
    assert line["host_clock"]["step_p50_ms_over_the_hooks"] > 0
    assert line["host_clock"]["t_end_less_t_begin_s"] > 0
