"""The benchmark's tests of what the OLMoE configuration brought (PR 27),
run by hand beside ``test_benchmark.py`` (which holds ``BENCHMARK.json``
and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_moe.py`` against numbers worked by hand, the three readers on
hand-made runs, and one CPU rehearsal of a sparse cell through the whole
chain at a toy size. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops_moe  # noqa: E402
import peaks  # noqa: E402
import run as harness  # noqa: E402


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_olmoe_parameters_and_operations_by_hand():
    c = _config("olmoe-1b-7b-d2")
    m = c["model"]
    # a layer: attention 4 * 2048^2, router 2048 * 64, 64 experts of
    # 3 * 2048 * 1024, two norms and the two QK-norm scales of 2048 each
    layer = 4 * 2048**2 + 2048 * 64 + 64 * 3 * 2048 * 1024 + 4 * 2048
    outside = 2 * 50304 * 2048 + 2048
    n = flops_moe.n_params(m)
    assert n["total"] == outside + 2 * layer
    active_layer = layer - 56 * 3 * 2048 * 1024
    assert n["active"] == outside + 2 * active_layer
    assert c["arithmetic"]["parameters"] == n["total"]
    # the published model: 6.92 B parameters, 1.28 B active a token
    full = flops_moe.n_params(dict(m, num_layers=c["published"][
        "num_hidden_layers"]))
    assert abs(full["total"] / 1e9 - 6.92) < 0.005
    assert abs(full["active"] / 1e9 - 1.28) < 0.005
    per_token = flops_moe.train_flops_per_token(m, 4096)
    by_hand = 6 * (
        2 * (4 * 2048**2 + 2048 * 64 + 8 * 3 * 2048 * 1024) + 2048 * 50304
    ) + 6 * 2 * 4096 * 2048
    assert per_token == by_hand and abs(per_token / 1e9 - 1.526) < 0.001
    # the configuration file holds the source's widths, and only the
    # depth differs
    for key, value in c["published"].items():
        if key in c and key != "num_hidden_layers":
            assert c[key] == value, key
    assert (m["model_dim"], m["num_heads"], m["mlp_dim"], m["num_experts"],
            m["moe_top_k"], m["vocab_size"], m["max_seq_len"]) == (
        2048, 16, 1024, 64, 8, 50304, 4096)


def test_grouped_matmul_work_by_hand():
    m = _config("olmoe-1b-7b-d2")["model"]
    w = flops_moe.grouped_matmul_work(m, 8192)
    rows = 8192 * 8
    # three projections, each one matmul forward and two backward
    assert w["flops"] == 9 * 2 * rows * 2048 * 1024
    assert w["bytes"] == 9 * 2 * (
        rows * 2048 + rows * 1024 + 64 * 2048 * 1024)
    p = peaks.peaks("TPU v5 lite")
    # 2.47 TFLOP against 6.0 GB: the matrix unit bounds it
    assert w["flops"] / p["bf16_flops"] > w["bytes"] / p["hbm_bytes_per_s"]


def _run(pipeline_open, pipeline, config="olmoe-1b-7b-d2", trace=None):
    config = _config(config)
    return SimpleNamespace(
        cell={"batch": 2, "seq": 4096, "moe": True},
        config=config, hook=harness.load_hook("x", config), trace=trace,
        peak=peaks.peaks("TPU v5 lite"),
        window={"pipeline_open": pipeline_open, "pipeline": pipeline,
                # the host's hooks: no reader of device time counts them
                "trace": {"step_begin": 30, "step_end": 51}},
    )


def test_readers_on_hand_made_runs(capsys):
    mods = harness.load_layer_metrics()
    drop, load, gmm = (mods[n] for n in (
        "moe.drop_rate_pct", "moe.max_expert_load",
        "kernel.moe_gmm_roofline"))
    for mod in (drop, load, gmm):
        assert mod.CELLS({"moe": True}) and not mod.CELLS({"batch": 2})
    opened = {"moe_reports": 2, "moe_drop_rate_sum": 0.0,
              "moe_max_load_sum": 3.0}
    closed = {"moe_reports": 6, "moe_drop_rate_sum": 0.02,
              "moe_max_load_sum": 9.0}
    run = _run(opened, closed)
    assert abs(drop.read(run) - 0.5) < 1e-12
    assert abs(load.read(run) - 1.5) < 1e-12
    # a program without the counters (the parent), or no report in the
    # window: nothing, and no error
    for a, b in (({}, {}), (opened, opened), ({"steps_ahead": 1},) * 2):
        assert drop.read(_run(a, b)) is None
        assert load.read(_run(a, b)) is None
    assert gmm.read(run) is None  # no trace
    work = flops_moe.grouped_matmul_work(run.config["model"], 8192)
    least = 2 * 20 * work["flops"] / 197e12
    ops = [
        {"name": "%ragged-dot-none.3", "count": 40, "total_s": 2 * least,
         "self_s": 2 * least, "about": "custom_call_target=tpu_custom_call"},
        {"name": "%ragged-dot-metadata.1", "count": 40, "total_s": 0.0,
         "self_s": 0.0, "about": "custom_call_target=tpu_custom_call"},
        {"name": "%jvp__.7", "count": 40, "total_s": 0.5, "self_s": 0.5,
         "about": "custom_call_target=tpu_custom_call"},
        # a fusion that reads a grouped matmul's result names it in its
        # HLO text: not a grouped matmul
        {"name": "%fusion.1", "count": 40, "total_s": 1.0, "self_s": 1.0,
         "about": "hlo=bf16[8,8] fusion(bf16[8,8] %ragged-dot-none.3)"},
    ]
    # 20 whole steps in the traced stretch, whatever the hooks counted
    trace = {"devices": [{"ops": ops, "steps": 20}]}
    traced = _run(opened, closed, trace=trace)
    assert abs(gmm.read(traced) - 50.0) < 1e-9
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["roofline"]["bound"] == "flops"
    assert abs(line["other_custom_call_seconds"] - 0.5) < 1e-12
    # a family whose step runs no grouped matmul (the dense block's)
    assert gmm.read(_run(
        opened, closed, config="gpt2-124m", trace=trace)) is None
    trace = {"devices": [{"ops": ops[2:], "steps": 20}]}
    assert gmm.read(_run(opened, closed, trace=trace)) is None


def test_cpu_rehearsal_of_a_sparse_cell(capsys):
    res = harness.run_cell(
        "toy-olmoe.steady", seed=3000000019, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    # the notes count the sparse model (the toy's "flops": "flops_moe"):
    # all 8 experts of a layer are held and a token passes through all 8
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    layer = 4 * 64**2 + 64 * 8 + 8 * 3 * 64 * 32 + 4 * 64
    assert notes["n_params"] == notes["active_params"] == (
        2 * 256 * 64 + 64 + 2 * layer)
    assert notes["flops_per_token"] == 6.0 * (
        2 * (layer - 4 * 64) + 64 * 256) + 6.0 * 2 * 64 * 64
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-olmoe.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window)
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # eight of eight experts a token: every expert gets the same
    assert abs(mods["moe.max_expert_load"].read(run) - 1.0) < 1e-6
