"""The benchmark's tests of what the Phi-4-mini-flash configuration
brought (PR 53), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_phi4flash.py`` against numbers worked by hand (each layer kind
counted once a layer of its kind at its own widths; 3.85 B on the published
pattern, 697 M held), the configuration file against the source, the four
new readers on hand-made runs, and one CPU rehearsal of the cell through
the whole chain. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_phi4flash as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "phi4-mini-flash-d6"
CELL = f"{NAME}.steady"
T, W = 16384, 512


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one layer of each kind, by hand, at the published widths
NORM = 2 * 2560  # a LayerNorm's weight and bias
MAMBA = (
    2560 * 10240  # in_proj: [x | z]
    + 4 * 5120 + 5120  # four taps a channel, and a bias
    + 5120 * (160 + 16 + 16)  # x_proj: [delta | B | C]
    + 160 * 5120 + 5120  # dt_proj and its bias
    + 5120 * 16 + 5120  # A_log, D
    + 5120 * 2560  # out_proj
)
ATTN = (
    2560 * (2560 + 1280 + 1280) + (2560 + 1280 + 1280)  # Wqkv and its bias
    + 2560 * 2560 + 2560  # out_proj and its bias
    + 4 * 64 + 128  # four lambda vectors, the pair norm
)
GMU = 2 * 2560 * 5120
CROSS = 2 * (2560 * 2560 + 2560) + 4 * 64 + 128
MLP = 3 * 2560 * 10240
PUBLISHED_PATTERN = "S-W-" * 8 + "S-*-" + "U-C-" * 7
# pairs a head sees of one row: through the window, and causally
SEEN = T * W - W * (W - 1) // 2
HALF = T * T // 2


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (p["S"], p["W"], p["*"], p["U"], p["C"], p["-"]) == (
        MAMBA + NORM, ATTN + NORM, ATTN + NORM, GMU + NORM, CROSS + NORM,
        MLP + NORM,
    )
    assert (MAMBA, ATTN, GMU, CROSS, MLP) == (
        41241600, 19668864, 26214400, 13112704, 78643200
    )
    held = (
        2 * MAMBA + 2 * ATTN + GMU + CROSS + 6 * MLP + 12 * NORM
        + 25088 * 2560 + NORM
    )
    c = family.count(m, T)
    assert c["params"] == c["active_params"] == held == 697299072
    # the published model: 9 scan layers, 8 window layers and the full
    # one, 7 memory units, 7 cross-attentions, the whole tied table
    pub = dict(m, layer_pattern=PUBLISHED_PATTERN, num_layers=64,
               vocab_size=200064)
    whole = family.count(pub, T)["params"]
    assert whole == (
        9 * MAMBA + 9 * ATTN + 7 * GMU + 7 * CROSS + 32 * MLP + 64 * NORM
        + 200064 * 2560 + NORM
    ) == 3852562944  # the card's "3.8B"
    assert PUBLISHED_PATTERN == _config()["reduced_from"]["layer_pattern"][0]


def test_operations_by_hand():
    m = _config()["model"]
    c = family.count(m, T)
    # a differential layer's scores and values a token, forward: 20 pairs,
    # two score maps over 64 and two applications to 128 wide values
    full = 20 * (2 * 2 * 64 + 2 * 2 * 128) * HALF / T
    window = 20 * (2 * 2 * 64 + 2 * 2 * 128) * SEEN / T
    assert family.attention_flops_per_token(m, T, 0) == full
    assert family.attention_flops_per_token(m, T, W) == window
    scan = 21.0 * 5120 * 16
    mm = {
        "S": MAMBA - (4 * 5120 + 5120 + 5120 + 5120 * 16 + 5120),
        "W": ATTN - (2560 + 1280 + 1280 + 2560 + 4 * 64 + 128),
        "U": GMU, "C": 2 * 2560 * 2560, "-": MLP,
    }
    assert c["by_kind"] == {
        "S": 2 * (6.0 * mm["S"] + scan),
        "W": 6.0 * mm["W"] + 3 * window,
        "*": 6.0 * mm["W"] + 3 * full,
        "U": 6.0 * mm["U"],
        "C": 6.0 * mm["C"] + 3 * full,
        "-": 6 * 6.0 * mm["-"],
        "head": 6.0 * 2560 * 25088,
    }
    assert c["train_flops_per_token"] == sum(c["by_kind"].values())
    assert round(c["train_flops_per_token"] / 1e9, 2) == 4.96
    # the six feed-forwards 57 %, the head 8 %, full + cross scores 17 %
    share = {k: v / c["train_flops_per_token"] for k, v in
             c["by_kind"].items()}
    assert 0.56 < share["-"] < 0.58 and 0.07 < share["head"] < 0.08
    assert 1.4 < 3 * full / (3 * 40 * 4 * 64 * HALF / T) < 1.6  # 1.5 x


def test_step_work_by_hand():
    m = _config()["model"]
    work = family.step_work(m, 1, T)
    plain = flops.attention_kernel_work(1, 40, T, 64)
    assert work["grouped_matmul"] is None
    assert work["attention_window"] == {
        "flops": 1.5 * plain["flops"] * SEEN / HALF, "bytes": plain["bytes"],
    }
    assert work["attention"] == {
        "flops": 1.5 * plain["flops"] * (2 + SEEN / HALF),
        "bytes": 3 * plain["bytes"],
    }
    # the window sees 6 % of the causal pairs
    assert 0.06 < SEEN / HALF < 0.062
    # two scans: 21 operations an element; 22 bytes a token and channel
    # (x, dt, y; x, dt, dy, dx, d dt) and 12 a token and state
    assert work["selective_scan"] == {
        "flops": 2 * 21.0 * T * 5120 * 16,
        "bytes": 2.0 * T * (5120 * 22 + 16 * 12),
    }
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    roof = flops.roofline_seconds(work["selective_scan"], peak)
    assert roof["bound"] == "bytes" and 4.4e-3 < roof["seconds"] < 4.6e-3
    # a model without a window, without scans: nothing to read
    other = dict(m, layer_pattern="*-C-", num_layers=4, attn_window=0)
    bare = family.step_work(other, 1, T)
    assert bare["attention_window"] is None
    assert bare["selective_scan"] is None and bare["attention"]


def test_the_family_refuses_another_models_group():
    import pytest

    m = _config()["model"]
    for wrong in (
        dict(m, layer_pattern="S-W-S-*-U-E-"),
        dict(m, num_layers=10),
        dict(m, attn_window=0),
        dict(m, attn_kind=""),
        dict(m, rmsnorm=True),
        dict(m, tie_embeddings=False),
    ):
        with pytest.raises(ValueError):
            family.count(wrong, T)


def test_the_configuration_file_against_the_source():
    c = _config()
    m, pub = c["model"], c["published"]
    # every number of the source under the same key, but what was cut
    for key, value in pub.items():
        if key not in c["reduced"]:
            assert c[key] == value, key
    assert c["reduced"] == [
        "num_hidden_layers", "num_layers", "layer_pattern", "vocab_size",
    ]
    assert (pub["num_hidden_layers"], c["num_hidden_layers"]) == (32, 6)
    assert (pub["vocab_size"], c["vocab_size"]) == (200064, 25088)
    for key, (before, after) in c["reduced_from"].items():
        assert key in c["reduced"]
        assert after == (c[key] if key in pub else m[key]), key
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["num_kv_heads"], m["dense_mlp_dim"],
        m["attn_window"], m["norm_eps"], m["tie_embeddings"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["intermediate_size"],
        pub["sliding_window"], pub["layer_norm_eps"],
        pub["tie_word_embeddings"],
    )
    assert m["attn_head_dim"] * m["num_heads"] == pub["hidden_size"]
    assert m["sscan_inner"] == 2 * pub["hidden_size"]  # expand 2
    assert m["sscan_dt_rank"] == -(-pub["hidden_size"] // 16)
    assert (m["sscan_state"], m["sscan_conv"]) == (16, 4)
    assert not pub["mlp_bias"] and m["swiglu"] and not m["rmsnorm"]
    assert m["positions"] == "none" and m["attn_kind"] == "diff"
    # the cut: published layers 14-19, every kind once and in order
    assert (m["layer_pattern"], m["first_layer"]) == ("S-W-S-*-U-C-", 14)
    assert m["num_layers"] == 2 * c["num_hidden_layers"] == 12
    # an eighth of the vocabulary, in whole lane tiles
    assert m["vocab_size"] == 25088 == 196 * 128
    assert 25008 == pub["vocab_size"] // 8 <= m["vocab_size"]
    assert c["arithmetic"]["parameters"] == family.count(m, T)["params"]
    assert c["arithmetic"]["published_parameters"] == 3852562944
    for item in (
        "family", "sscan", "biases", "head_pairing", "window",
        "shared_sources", "initial_values", "param_dtype", "optimizer",
        "weight_decay",
    ):
        assert item in c["assumed"], item
    assert "8 chips" in c["deployment"]
    assert m["remat"] is c["strategy"]["remat"] is True
    assert "remat" in c["arithmetic"]
    cell = harness.load_cell(CELL)
    assert (cell["batch"], cell["seq"], cell["chips"]) == (1, T, 1)
    assert cell["seq"] == m["max_seq_len"]
    assert cell["trace"]["steps"] == 20 and not cell["kill"]
    assert "no_twin" in cell and cell["warmup"]["min_steps"] == 100


def test_the_new_readers():
    mods = harness.load_layer_metrics()
    roof = mods["kernel.sscan_roofline"]
    share = mods["sscan.kernel_sites_share"]
    serial = mods["sscan.serial_steps"]
    passes = mods["attn.diff_score_passes"]
    assert [(m.LAYER, m.UNIT, m.MOVES) for m in (roof, share, serial,
                                                  passes)] == [
        ("kernels", "%", "tokens_per_s"), ("kernels", "%", "tokens_per_s"),
        ("step program", "steps", "tokens_per_s"),
        ("kernels", "passes", "tokens_per_s"),
    ]
    assert roof.CEILING == 100.0
    assert not any(hasattr(m, "CEILING") for m in (share, serial, passes))
    config = _config()

    def run(closed, config=config):
        return SimpleNamespace(config=config, window={"pipeline": closed})

    # the cell's step under remat: two scans, each forward twice
    stats = {"sscan_sites": 2, "sscan_kernel_sites": 2,
             "sscan_serial_steps": 2 * 4 * T,
             "attn_diff_pairs": 60, "attn_diff_score_calls": 60}
    assert share.read(run(stats)) == 100.0
    assert serial.read(run(stats)) == 131072.0
    assert passes.read(run(stats)) == 1.0
    assert share.read(run(dict(stats, sscan_kernel_sites=1))) == 50.0
    assert passes.read(run(dict(stats, attn_diff_score_calls=120))) == 2.0
    # a program without the counters (the parent's), a step that was not
    # traced, a configuration of another family: nothing
    for mod in (share, serial, passes):
        assert mod.read(run({"moe_reports": 14})) is None
        assert mod.read(run({})) is None
        assert mod.read(SimpleNamespace(config=config, window={})) is None
        assert mod.read(run(stats, _config("trinity-mini-d5"))) is None
    assert share.read(run({"sscan_sites": 0, "sscan_kernel_sites": 0})) is None
    assert share.read(run({"sscan_sites": 2})) is None
    assert passes.read(run({"attn_diff_pairs": 60})) is None
    names = (
        CELL, "trinity-mini-d5.steady", "ling-3.0-flash-d7.steady",
        "nemotron3-nano-30b-a3b-d9.steady", "gpt2-124m.steady",
    )
    for mod in (roof, share, serial, passes):
        assert [mod.CELLS(harness.load_cell(n)) for n in names] == [
            True, False, False, False, False
        ]
    # the accepted readers whose rules take the cell, and the one whose
    # rule knows the letters M and G only
    takes = {
        "kernel.attn_window_roofline": True,
        "attn.window_blocks_walked_pct": True, "opt.q8_tiles_share": True,
        "attn.fwd_kernel_runs_per_step": True,
        "attn.score_lanes_used_pct": False,
        "conv.kernel_sites_share": False, "gdn.serial_chunk_steps": False,
        "kernel.moe_gmm_roofline": False,
    }
    cell = harness.load_cell(CELL)
    assert {n: mods[n].CELLS(cell) for n in takes} == takes

    # the roofline reader on a hand-made trace: the sscan kernels alone
    def op(name, seconds):
        return {"name": name, "about": "custom-call tpu_custom_call",
                "total_s": seconds, "count": 1}

    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    work = family.step_work(config["model"], 1, T)["selective_scan"]
    least = work["bytes"] / peak["hbm_bytes_per_s"]  # bound by bytes
    device = {"steps": 2, "ops": [
        op("%sscan_fwd.4", 4 * least), op("%sscan_bwd.2", 4 * least),
        op("%flash_attn_fwd.5", 1.0), op("%conv_silu_fwd.1", 1.0),
        op("%fusion.7", 5.0),
    ]}
    traced = SimpleNamespace(
        config=config, cell=cell, hook=family, peak=peak,
        trace={"devices": [device]},
    )
    assert abs(roof.read(traced) - 25.0) < 1e-9
    # no such kernel in the trace (the plain statement), no trace, a
    # family module that counts no such work (every other family's)
    device["ops"] = device["ops"][2:]
    assert roof.read(traced) is None
    traced.trace = None
    assert roof.read(traced) is None
    traced.trace = {"devices": [device]}
    import flops_afmoe

    traced.hook = flops_afmoe
    traced.config = _config("trinity-mini-d5")
    traced.cell = harness.load_cell("trinity-mini-d5.steady")
    assert roof.read(traced) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-phi4flash.steady", seed=3000000053, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # S-W-S-*-U-C- at width 64: scans of 128 channels x 16 states through
    # a rank of 4, 8 heads on 4 of 8, feed-forwards of 128, one table
    norm = 2 * 64
    mamba = (2 * 64 * 128 + 5 * 128 + 128 * (4 + 32) + 4 * 128 + 128
             + 128 * 16 + 128 + 128 * 64)
    attn = 64 * 128 + 128 + 64 * 64 + 64 + 4 * 8 + 16
    cross = 2 * (64 * 64 + 64) + 4 * 8 + 16
    assert notes["n_params"] == (
        256 * 64 + norm + 2 * mamba + 2 * attn + 2 * 64 * 128 + cross
        + 6 * 3 * 64 * 128 + 12 * norm
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-phi4flash.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-phi4flash.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    # the scans' kernels are interpreted on the CPU, and counted; under
    # remat a scan's forward is walked twice: 2 x 4 x 128 steps
    assert mods["sscan.kernel_sites_share"].read(run) == 100.0
    assert mods["sscan.serial_steps"].read(run) == 1024.0
    assert mods["attn.diff_score_passes"].read(run) == 1.0
    pipeline = window["pipeline"]
    assert (pipeline["xdec_memory_reads"], pipeline["xdec_kv_reads"]) == (1, 1)
    # on the CPU the attention is the jnp path: no kernel site is counted
    assert mods["attn.window_blocks_walked_pct"].read(run) is None
