"""The benchmark's tests of what the Ouro-2.6B configuration brought
(PR 57), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_ouro.py`` against numbers worked by hand (a weight held once and
used ``ut_steps`` times: 2,667,974,657 parameters on the published depth,
509,661,185 held, 12.2 GFLOP a token), the configuration file against the
source, the two new readers on hand-made runs, and one CPU rehearsal of
the cell through the whole chain. Nothing here is a speed.
"""

import json
import math
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_ouro as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "ouro-2.6b-d6"
CELL = f"{NAME}.steady"
T, PASSES = 8192, 4
# one published block, by hand: four projections of 2048 x 16 x 128, a
# gated feed-forward of 5632, four norm weights
BLOCK = 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048
TABLES = 2 * 49152 * 2048
REST = 2048 + 2049  # the final norm; the exit gate and its bias


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_family_counts_the_published_model_and_the_cut():
    model = _config()["model"]
    assert BLOCK == 51_388_416
    held = family.count(model, T)
    assert held["params"] == held["active_params"] == (
        6 * BLOCK + TABLES + REST
    ) == 509_661_185
    deep = dict(model, num_layers=96, layer_pattern="*-" * 48)
    assert family.count(deep, T)["params"] == (
        48 * BLOCK + TABLES + REST
    ) == 2_667_974_657
    assert _config()["arithmetic"]["parameters"] == held["params"]
    assert _config()["arithmetic"]["published_parameters"] == 2_667_974_657


def test_a_tokens_operations_count_every_pass():
    model = _config()["model"]
    got = family.count(model, T)
    matmul = 6 * (BLOCK - 4 * 2048)  # the layers' matrices, one pass
    scores = 6 * 2 * 2 * 16 * 128 * T // 2  # Q K^T and P V, causal half
    head = 49152 * 2048
    forward = 2 * matmul + scores + 2 * head
    assert got["train_flops_per_token"] == PASSES * 3 * forward
    assert abs(got["train_flops_per_token"] / 1e9 - 12.23) < 0.01
    # and not the 6 N of a model that uses a weight once: 3.06 G
    assert got["train_flops_per_token"] > 3.9 * 6 * got["params"]
    share = {k: v / got["train_flops_per_token"]
             for k, v in got["by_kind"].items()}
    assert abs(share["head"] - 0.1975) < 1e-3
    assert abs(share["*"] + share["-"] + share["head"] - 1.0) < 1e-12


def test_the_attention_work_is_24_layers_of_one():
    model = _config()["model"]
    work = family.step_work(model, 1, T)
    one = flops.attention_kernel_work(1, 16, T, 128)
    assert work["attention"] == {k: 24 * v for k, v in one.items()}
    assert work["grouped_matmul"] is None
    two_rows = family.step_work(model, 2, T)["attention"]
    assert two_rows["flops"] == 2 * work["attention"]["flops"]


@pytest.mark.parametrize("nonsense", [
    dict(ut_steps=1), dict(mixer_out_norm=False), dict(tie_embeddings=True),
    dict(layer_pattern="*-*-*-*-*-*E"), dict(num_layers=10),
])
def test_the_family_refuses_another_shape(nonsense):
    with pytest.raises(ValueError):
        family.count(dict(_config()["model"], **nonsense), T)


def test_the_configuration_is_the_source_cut_in_depth_alone():
    config = _config()
    published, model = config["published"], config["model"]
    changed = {k for k in published if config.get(k) != published[k]}
    assert changed == {"num_hidden_layers"}
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_layers", "layer_pattern",
    }
    assert (model["model_dim"], model["num_heads"], model["num_kv_heads"],
            model["attn_head_dim"], model["dense_mlp_dim"],
            model["vocab_size"], model["ut_steps"]) == (
        published["hidden_size"], published["num_attention_heads"],
        published["num_key_value_heads"], published["head_dim"],
        published["intermediate_size"], published["vocab_size"],
        published["total_ut_steps"],
    )
    assert model["norm_eps"] == published["rms_norm_eps"]
    assert model["rope_theta"] == published["rope_theta"]
    assert model["tie_embeddings"] is published["tie_word_embeddings"]
    assert model["layer_pattern"] == "*-" * config["num_hidden_layers"]
    check = config["reference_check"]
    program, control = check["program_abs_diff"], check["float8_abs_diff"]
    assert len(program) >= 16 and len(control) >= 16
    assert max(program) < check["tolerance"] < sorted(control)[2]


def test_the_new_readers_on_hand_made_runs():
    mods = harness.load_layer_metrics()
    passes, entropy = (
        mods["ut.layer_passes_per_step"], mods["ut.exit_entropy_nats"]
    )
    config, cell = _config(), harness.load_cell(CELL)
    assert passes.CELLS(cell) and entropy.CELLS(cell)
    other = harness.load_cell("trinity-mini-d5.steady")
    assert not passes.CELLS(other) and not entropy.CELLS(other)
    # the accepted readers whose rule takes the new cell, and some whose
    # rule does not
    takes = {
        "opt.q8_tiles_share": True, "attn.edge_tiles_multiplied_pct": True,
        "attn.fwd_kernel_runs_per_step": True,
        "attn.window_blocks_walked_pct": False,
        "attn.score_lanes_used_pct": False, "moe.max_expert_load": False,
        "kernel.moe_gmm_roofline": False, "sscan.serial_steps": False,
    }
    assert {n: mods[n].CELLS(cell) for n in takes} == takes
    run = SimpleNamespace(config=config, cell=cell, window={
        "pipeline_open": {"ut_reports": 8, "ut_entropy_sum": 8 * 1.3},
        "pipeline": {"ut_reports": 12, "ut_entropy_sum": 8 * 1.3 + 4 * 1.2,
                     "ut_layer_passes": 48},
    })
    assert passes.read(run) == 48.0
    assert abs(entropy.read(run) - 1.2) < 1e-12
    assert 0 < entropy.read(run) <= math.log(PASSES)
    # no report inside the window; a program without the counters (the
    # parent's); a configuration that runs its layers once
    run.window["pipeline"]["ut_reports"] = 8
    assert entropy.read(run) is None
    run.window = {"pipeline": {"moe_reports": 3}, "pipeline_open": {}}
    assert passes.read(run) is None and entropy.read(run) is None
    run.window = {"pipeline": {"ut_layer_passes": 48, "ut_reports": 1,
                               "ut_entropy_sum": 1.0}}
    run.config = _config("trinity-mini-d5")
    assert passes.read(run) is None and entropy.read(run) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-ouro.steady", seed=3000000057, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # three blocks at width 64: 4 heads of 16, feed-forwards of 96, two
    # tables of 256 rows, the final norm, the gate
    assert notes["n_params"] == (
        3 * (4 * 64 * 64 + 3 * 64 * 96 + 4 * 64) + 2 * 256 * 64 + 64 + 65
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-ouro.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-ouro.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    pipeline = window["pipeline"]
    assert (pipeline["ut_steps"], pipeline["ut_layer_passes"],
            pipeline["ut_exit_heads"]) == (4, 24, 4)
    assert mods["ut.layer_passes_per_step"].read(run) == 24.0
    nats = mods["ut.exit_entropy_nats"].read(run)
    assert nats is not None and 0.0 < nats <= math.log(4)
    # on the CPU the attention is the jnp path: no kernel site is counted
    assert mods["attn.edge_tiles_multiplied_pct"].read(run) is None
