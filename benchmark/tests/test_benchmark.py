"""The benchmark's own tests, run by hand (not under ``tests/``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

They cover the yardstick's arithmetic on inputs worked by hand, the
harness's refusals, that ``BENCHMARK.json`` and the data files agree, and
one CPU rehearsal of a kill cell end to end at a toy size. Nothing here is
a speed: a rehearsal's numbers come from the CPU and are thrown away.
"""

import glob
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

import arith  # noqa: E402
import flops  # noqa: E402
import peaks  # noqa: E402
import run as harness  # noqa: E402
import xplane  # noqa: E402


def _bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# -- flops.py against hand-worked numbers ----------------------------------
def test_parameter_counts_by_hand():
    # 124M: 50257*768 + 1024*768 + 12*(4*768^2 + 2*768*3072 + 3072 + 768
    #       + 4*768) + 2*768
    m = _config("gpt2-124m")["model"]
    per_layer = 4 * 768**2 + 2 * 768 * 3072 + 3072 + 768 + 4 * 768
    assert flops.n_params(m) == 50257 * 768 + 1024 * 768 + 12 * per_layer \
        + 2 * 768
    assert 124.3e6 < flops.n_params(m) < 124.5e6
    x = _config("gpt2-xl-d12")["model"]
    assert 450e6 < flops.n_params(x) < 452e6


@pytest.mark.parametrize(
    "name,gflop", [("gpt2-124m", 0.80), ("gpt2-xl-d12", 2.82)]
)
def test_flops_per_token_by_hand(name, gflop):
    m = _config(name)["model"]
    got = flops.train_flops_per_token(m, 1024)
    by_hand = 6 * flops.n_params(m) + 6 * m["num_layers"] * 1024 * \
        m["model_dim"]
    assert got == by_hand
    assert abs(got / 1e9 - gflop) < 0.01


def test_mfu_and_roofline_by_hand():
    m = _config("gpt2-124m")["model"]
    p = peaks.peaks("TPU v5 lite")
    # 197e12 / 0.803e9 flops a token = 245k tokens/s at 100%
    per_token = flops.train_flops_per_token(m, 1024)
    full = p["bf16_flops"] / per_token
    assert abs(flops.mfu_pct(full / 2, per_token, p["bf16_flops"]) - 50) \
        < 1e-9
    # one head, one row, T=1024, D=64: 6 matmuls of 2*T*T*D/2
    w = flops.attention_kernel_work(1, 1, 1024, 64)
    assert w["flops"] == 6 * 1024 * 1024 * 64
    assert w["bytes"] == 11 * 1024 * 64 * 2
    r = flops.roofline_seconds(w, p)
    assert r["bound"] == "flops"
    assert abs(r["seconds"] - 6 * 1024 * 1024 * 64 / 197e12) < 1e-15


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


# -- percentile and window arithmetic on synthetic step records -------------
def _records(times, step0=10, **extra):
    return [
        {"step": step0 + i, "t": t, "commits": 0, "staging": False,
         "stage_chunks": 0, **extra}
        for i, t in enumerate(times)
    ]


def test_window_summary_counts_all_work_over_all_time():
    times = [100.0 + 0.1 * i for i in range(21)]
    times[11:] = [t + 0.4 for t in times[11:]]  # one 0.5 s step
    rec = _records([99.8, 99.9] + times + [103.0])
    s = arith.window_summary(
        arith.window_records(rec, 100.0, 102.4), tokens_per_step=1000
    )
    assert s["steps"] == 20 and s["samples"] == 20
    assert abs(s["seconds"] - 2.4) < 1e-9
    assert abs(s["tokens_per_s"] - 20 * 1000 / 2.4) < 1e-6
    assert abs(s["step_p50_ms"] - 100.0) < 1e-6
    # 19 samples of 100 ms and one of 500: p95 lies between them
    assert abs(s["step_p95_ms"] - (100 + 400 * 0.05)) < 1e-6
    assert arith.percentile([1, 2, 3, 4], 50) == 2.5
    with pytest.raises(ValueError):
        arith.window_summary(arith.window_records(rec, 500.0, 600.0), 1000)


def test_saves_begun_and_commit_lags():
    # saves fall due every 50 steps and take 25 to commit; the one due at
    # 150 is skipped (the saver is busy): no chunk moves after its hook
    rec, chunks, commits = [], 0, 0
    for step in range(95, 260):
        live = [b for b in (100, 200, 250) if b < step <= b + 25]
        if live:
            chunks += 1
        if step in (125, 225):
            commits += 1
        rec.append({"step": step, "t": float(step), "commits": commits,
                    "stage_chunks": chunks,
                    "staging": bool(live) and step not in (125, 225)})
    assert arith.saves_begun(rec) == [100, 200, 250]
    assert arith.commit_lags(rec) == [25, 25]


def test_profiler_steps_leave_with_their_children():
    # three steps of 100 ms on thread 1, each with a compute child; the
    # profiler was started in the hook of the second (after its compute)
    spans = []
    for i in range(3):
        t0 = int(1e9 * (10 + 0.1 * i))
        spans.append(["step", t0, int(0.1e9), 0, 1])
        spans.append(["compute", t0 + 1000, int(0.08e9), 1, 1])
    spans.append(["prefetch_pull", int(10.1e9), int(0.01e9), 0, 2])
    kept = harness.spans_without_profiler_steps(spans, [[10.19, 10.195]])
    assert [s[0] for s in kept].count("step") == 2
    assert [s[0] for s in kept].count("compute") == 2
    assert any(s[0] == "prefetch_pull" for s in kept)
    assert harness.spans_without_profiler_steps(spans, []) == spans


# -- the trace reducer on a hand-built trace ---------------------------------
def _plane():
    # five executions of one program, 250 ns apart: the first and the last
    # may be cut by the capture, so the stretch is the three between them
    ops, modules = [], []
    for k in range(5):
        t = 250.0 * k
        modules.append(("jit_f(1)", t, 220.0, {}))
        ops += [
            # a while loop holding two children, then a gap, then a kernel
            ("while.1", t, 100.0, {}),
            ("fusion.1", t + 10.0, 30.0,
             {"hlo_category": "convolution fusion"}),
            ("custom-call.7", t + 50.0, 40.0,
             {"custom_call_target": "tpu_custom_call"}),
            ("fusion.1", t + 150.0, 50.0,
             {"hlo_category": "convolution fusion"}),
            ("custom-call.7", t + 200.0, 20.0,
             {"custom_call_target": "tpu_custom_call"}),
        ]
    return [
        {"name": "/host:CPU", "lines": [{"name": "python", "events": []}]},
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": modules},
            {"name": "XLA Ops", "events": ops},
        ]},
    ]


def test_reducer_on_a_hand_built_trace():
    r = xplane.reduce_planes(_plane())
    assert [p["plane"] for p in r["listing"]] == ["/host:CPU", "/device:TPU:0"]
    d = r["devices"][0]
    # the stretch: [250, 1000) ns, three whole steps; in each, busy
    # [0,100) + [150,220) = 170 ns of 250
    assert d["steps"] == r["steps"] == 3
    assert d["step_programs"] == ["jit_f(1)"] and d["step_executions"] == 5
    assert abs(d["window_s"] - 750e-9) < 1e-15
    assert abs(d["busy_s"] - 510e-9) < 1e-15
    assert abs(r["busy_s"] - 510e-9) < 1e-15 and r["window_s"] == d["window_s"]
    # the whole file, as it was summed before: five executions, edge to edge
    assert abs(d["whole_file"]["busy_s"] - 850e-9) < 1e-15
    assert abs(d["whole_file"]["span_s"] - 1220e-9) < 1e-15
    ops = {o["name"]: o for o in d["ops"]}
    assert ops["while.1"]["self_s"] == pytest.approx(3 * 30e-9)
    assert ops["fusion.1"]["total_s"] == pytest.approx(3 * 80e-9)
    assert ops["fusion.1"]["count"] == 6
    k = xplane.kernel_seconds(d, ["tpu_custom_call"])
    assert k["seconds"] == pytest.approx(3 * 60e-9) and k["count"] == 6
    # the gaps, by the operation that ended each: 50 ns before the second
    # fusion.1, 30 ns before the next execution's while.1 (the last of
    # them ended by the execution that closes the stretch)
    assert [g["before"] for g in d["gaps"]] == ["fusion.1", "while.1"]
    assert d["gaps"][0]["seconds"] == pytest.approx(150e-9)
    assert d["gaps"][1]["seconds"] == pytest.approx(90e-9)
    assert d["gaps"][1]["count"] == 3
    assert d["modules"][0]["count"] == 3
    assert xplane.union_seconds([(0, 10), (5, 20), (30, 40)]) == \
        pytest.approx(30e-9)


def test_reducer_reads_a_recorded_trace(tmp_path):
    """A small trace recorded here, on the CPU: the reading path
    (``ProfileData``) works and finds no device plane to report."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    files = glob.glob(
        str(tmp_path / "plugins" / "profile" / "*" / "*.xplane.pb")
    )
    assert files
    r = xplane.reduce_planes(xplane.load(files[0]))
    assert any(p["plane"].startswith("/host") for p in r["listing"])
    assert r["devices"] == [] and "busy_s" not in r


# -- BENCHMARK.json and the data files agree --------------------------------
def test_benchmark_json_agrees_with_the_files():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    mods = harness.load_layer_metrics()
    e2e = {m["name"] for m in b["end_to_end"]}
    cells = {w["name"]: harness.load_cell(w["name"]) for w in b["workloads"]}
    for w in b["workloads"]:
        cell = cells[w["name"]]
        assert (w["config"], w["traffic"], w["chips"], w["why"]) == (
            cell["config"], cell["traffic"], cell["chips"], cell["why"]
        )
        assert len(w["why"]) <= 200
        harness.load_config(w["config"])
    for c in b["configs"]:
        assert harness.load_config(c["name"])["reduced"] == c["reduced"]
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
    assert {m["name"] for m in b["per_layer"]} == set(mods)
    for m in b["per_layer"]:
        mod = mods[m["name"]]
        assert (m["layer"], m["unit"], m["moves"]) == (
            mod.LAYER, mod.UNIT, mod.MOVES
        )
        assert m["moves"] in e2e
        want = [n for n, c in cells.items() if mod.CELLS(c)]
        assert sorted(m.get("workloads", cells)) == sorted(want)


# -- the harness refuses what does not parse ---------------------------------
def test_refuses_files_that_do_not_parse(tmp_path, monkeypatch):
    (tmp_path / "cells").mkdir()
    (tmp_path / "configs").mkdir()
    (tmp_path / "cells" / "bad.json").write_text("{not json")
    (tmp_path / "cells" / "thin.json").write_text('{"config": "x"}')
    with pytest.raises(harness.Refused, match="does not parse"):
        harness.load_cell("bad", str(tmp_path))
    with pytest.raises(harness.Refused, match="missing keys"):
        harness.load_cell("thin", str(tmp_path))
    with pytest.raises(harness.Refused, match="cannot read"):
        harness.load_cell("absent", str(tmp_path))
    with pytest.raises(harness.Refused, match="not a cell name"):
        harness.load_cell("../x", str(tmp_path))
    (tmp_path / "configs" / "bad.json").write_text("[1, 2]")
    with pytest.raises(harness.Refused, match="not a JSON object"):
        harness.load_config("bad", str(tmp_path))
    (tmp_path / "layer_metrics").mkdir()
    (tmp_path / "layer_metrics" / "x.broken.py").write_text("LAYER = 'l'\n")
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    with pytest.raises(harness.Refused, match="per-layer metric"):
        harness.load_layer_metrics()


def test_command_line_without_a_tpu_prints_no_result(capsys):
    """Outside the rehearsal below nothing steers the device: a run that
    finds no TPU exits non-zero and prints no result object."""
    here = os.path.join(HERE, "rehearsal")
    with pytest.raises(harness.Refused):
        harness.run_cell("toy.steady", 1, 0.5, False, data_dir=here)
    assert '"correct"' not in capsys.readouterr().out


# -- one kill cell end to end, at a toy size, on the CPU ---------------------
def test_cpu_rehearsal_of_a_kill_cell():
    res = harness.run_cell(
        "toy.save-kill-resume", seed=3000000011, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    assert set(res["metrics"]) == {
        "tokens_per_s", "step_p95_ms", "setup_s"
    }
    assert res["attempted"] > 10
