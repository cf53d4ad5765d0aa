"""The benchmark's tests of what the Olmo-Hybrid configuration brought
(PR 64), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_olmo_hybrid.py`` against numbers worked by hand (the published
7,430,870,688 parameters and the 928,862,196 held; each kind of entry
counted once an entry of its kind at its own widths; the chunk kernels'
least work at the stated 96 / 192), the configuration file against the
source, the two new readers on hand-made runs, and one CPU rehearsal of the
cell through the whole chain at a toy size. Nothing here is a speed.
"""

import functools
import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_olmo_hybrid as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "olmo-hybrid-7b-d4"
CELL = "olmo-hybrid-7b-d4.steady"


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one entry of each kind, by hand, at the published widths
LINEAR_MIXER = (
    2 * 3840 * 2880  # W_q, W_k: 30 heads of 96
    + 3 * 3840 * 5760  # W_v, W_g, W_o: 30 heads of 192
    + 2 * 3840 * 30  # W_a, W_b
    + (2880 + 2880 + 5760) * 4  # the three convolutions of 4, no bias
    + 2 * 30  # A_log, dt_bias
    + 192  # the gated norm's weight
)
ATTENTION_MIXER = 4 * 3840 * 3840 + 2 * 3840  # q, k, v, o; the q and k norms
MLP = 3 * 3840 * 11008
NORM = 3840  # one an entry, on its input or on its output


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (LINEAR_MIXER, ATTENTION_MIXER, MLP) == (
        88_750_332, 58_990_080, 126_812_160
    )
    assert (p["G"], p["*"], p["-"]) == (
        LINEAR_MIXER + NORM, ATTENTION_MIXER + NORM, MLP + NORM
    )
    linear_layer = LINEAR_MIXER + MLP + 2 * NORM
    attention_layer = ATTENTION_MIXER + MLP + 2 * NORM
    assert (linear_layer, attention_layer) == (215_570_172, 185_809_920)
    held = 3 * linear_layer + attention_layer + 2 * 12544 * 3840 + 3840
    assert family.count(m, 16384)["params"] == held == 928_862_196
    published = dict(
        m, num_layers=64, layer_pattern="G-G-G-*-" * 8, vocab_size=100352
    )
    assert family.count(published, 16384)["params"] == (
        24 * linear_layer + 8 * attention_layer + 2 * 100352 * 3840 + 3840
    ) == 7_430_870_688
    c = family.count(m, 16384)
    assert c["active_params"] == c["params"]  # dense


def test_operations_by_hand():
    m = _config()["model"]
    c = family.count(m, 16384)
    gdn_mm = 2 * 3840 * 2880 + 3 * 3840 * 5760 + 2 * 3840 * 30
    # a head and token of the chunked rule, forward: K K^T, the inverse,
    # U, W; Q K^T, the read-out, Q S; and the pass's two products
    local = (
        64 * 96 + 2 * 64 * 64 / 3 + 64 * (192 + 96)
        + 64 * 96 + 64 * 192 + 2 * 96 * 192
    )
    close = functools.partial(pytest.approx, rel=1e-12)  # a third in it
    assert family.chunk_local_flops_per_token(m) == close(30 * local)
    scan = 30 * (local + 4 * 96 * 192)
    assert family.scan_flops_per_token(m) == close(scan)
    by_kind = {
        "G": 3 * (6.0 * gdn_mm + 3.0 * scan),
        "*": 6.0 * 4 * 3840 * 3840 + 12.0 * 16384 * 30 * 128 / 2,
        "-": 4 * 6.0 * MLP,
        "head": 6.0 * 3840 * 12544,
    }
    assert c["by_kind"] == close(by_kind)
    total = sum(by_kind.values())
    assert c["train_flops_per_token"] == close(total)
    assert 5.69e9 < total < 5.71e9  # about 5.7 GFLOP a token
    share = {k: round(100 * v / total, 1) for k, v in by_kind.items()}
    assert share == {"G": 28.7, "*": 12.8, "-": 53.4, "head": 5.1}
    # at the published depth and vocabulary the head's share is the same
    published = dict(
        m, num_layers=64, layer_pattern="G-G-G-*-" * 8, vocab_size=100352
    )
    pub = family.count(published, 16384)
    assert round(
        100 * pub["by_kind"]["head"] / pub["train_flops_per_token"], 1
    ) == 5.1


def test_step_work_by_hand():
    m = _config()["model"]
    work = family.step_work(m, 1, 16384)
    assert work["grouped_matmul"] is None
    assert work["attention"] == {
        "flops": flops.attention_kernel_work(1, 30, 16384, 128)["flops"],
        "bytes": float(11 * 30 * 16384 * 128 * 2),
    }
    local = (
        64 * 96 + 2 * 64 * 64 / 3 + 64 * (192 + 96)
        + 64 * 96 + 64 * 192 + 2 * 96 * 192
    )
    # bytes a token of a head: k, v, beta, g in; U (float32), W, K, delta,
    # a out; q, k, g, V', the entered state in; o out
    wy_in = 2 * 96 + 2 * 192 + 8
    wy_out = 4 * 192 + 2 * 96 + 4 + 4 / 64 + 2 * 96
    read_in = 2 * 2 * 96 + 4 + 2 * 192 + 2 * 96 * 192 / 64
    read_out = 2 * 192
    a_token = (wy_in + wy_out + read_in + read_out) + (
        2 * wy_in + wy_out + 2 * read_in + read_out
    )
    assert work["gated_delta"] == pytest.approx({
        "flops": 3 * 3.0 * 30 * local * 16384,
        "bytes": 3 * float(16384 * 30 * a_token),
    }, rel=1e-12)
    # the bound that holds is HBM, by the published peaks of a v5e
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(work["gated_delta"], peak)["bound"] == (
        "bytes"
    )
    # no delta-rule entry: nothing to count
    dense = dict(m, num_layers=2, layer_pattern="*-")
    assert family.step_work(dense, 1, 1024)["gated_delta"] is None


def test_the_configuration_is_the_source_cut_in_depth_and_vocabulary():
    config = _config()
    pub, m = config["published"], config["model"]
    assert config["source"].startswith(
        "https://huggingface.co/allenai/Olmo-Hybrid-7B"
    )
    assert pub["num_hidden_layers"] == 32 and pub["vocab_size"] == 100352
    assert pub["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]
    ) * 8
    changed = {
        k for k in pub if k in config and config[k] != pub[k]
    }
    assert changed == {"num_hidden_layers", "vocab_size", "layer_types"}
    assert changed <= set(config["reduced"])
    assert config["layer_types"] == pub["layer_types"][:4]
    assert (m["model_dim"], m["mlp_dim"]) == (
        pub["hidden_size"], pub["intermediate_size"]
    )
    assert (m["num_heads"], m["num_kv_heads"], m["attn_head_dim"]) == (
        pub["num_attention_heads"], pub["num_key_value_heads"],
        pub["hidden_size"] // pub["num_attention_heads"],
    )
    assert (
        m["gdn_key_heads"], m["gdn_value_heads"], m["gdn_key_dim"],
        m["gdn_value_dim"], m["gdn_conv"],
    ) == (
        pub["linear_num_key_heads"], pub["linear_num_value_heads"],
        pub["linear_key_head_dim"], pub["linear_value_head_dim"],
        pub["linear_conv_kernel_dim"],
    )
    assert pub["linear_allow_neg_eigval"] and m["gdn_beta_scale"] == 2.0
    assert pub["rope_parameters"] == {"rope_theta": None}
    assert m["positions"] == "none" and not m["rope"]
    assert m["norm_eps"] == pub["rms_norm_eps"]
    assert m["layer_pattern"] == "G-G-G-*-" and m["num_layers"] == 8
    assert m["reordered_norm_kinds"] == "*"
    assert m["vocab_size"] == 12544 == 98 * 128 == pub["vocab_size"] // 8
    assert config["reduced_from"]["vocab_size"] == [100352, 12544]
    assert config["reduced_from"]["num_hidden_layers"] == [32, 4]
    for key in ("assumed", "deployment", "arithmetic"):
        assert config[key], key
    with open(os.path.join(BENCH, "cells", f"{CELL}.json")) as f:
        cell = json.load(f)
    assert (cell["chips"], cell["batch"], cell["seq"]) == (1, 1, 16384)
    assert len(cell["why"]) <= 200


def _run(pipeline, config=None):
    return SimpleNamespace(
        window={"pipeline": pipeline}, config=config or _config()
    )


def test_head_lanes_used_reads_the_counters():
    mods = harness.load_layer_metrics()
    reader = mods["gdn.head_lanes_used_pct"]
    # 96 + 192 stated in blocks of 128 + 256, three sites
    assert reader.read(_run(
        {"gdn_head_lanes": 3 * 384, "gdn_head_lanes_used": 3 * 288}
    )) == 75.0
    assert reader.read(_run(
        {"gdn_head_lanes": 864, "gdn_head_lanes_used": 864}
    )) == 100.0
    # a program without the counters (the parent), or no site in a kernel
    assert reader.read(_run({})) is None
    assert reader.read(_run({"gdn_head_lanes": 0})) is None
    assert reader.read(_run({"gdn_head_lanes": 384})) is None
    # heads of whole tiles: nothing to say
    qwen = _config("qwen3-next-80b-a3b-d4")
    assert reader.read(_run(
        {"gdn_head_lanes": 768, "gdn_head_lanes_used": 768}, qwen
    )) is None
    cells = {
        name: harness.load_cell(name)
        for name in ("qwen3-next-80b-a3b-d4.steady", CELL,
                     "ling-3.0-flash-d7.steady", "gpt2-124m.steady")
    }
    assert [reader.CELLS(c) for c in cells.values()] == [
        False, True, False, False
    ]
    roofline = mods["kernel.gdn_roofline"]
    assert [roofline.CELLS(c) for c in cells.values()] == [
        False, True, False, False
    ]


def test_gdn_roofline_reads_a_built_trace(capsys):
    """Four ``gdn_chunk`` kernels a layer and step on a hand-made plane:
    the share is the family module's least seconds over theirs."""
    mods = harness.load_layer_metrics()
    reader = mods["kernel.gdn_roofline"]
    config = _config()
    cell = harness.load_cell(CELL)
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    steps = 19
    work = family.step_work(config["model"], 1, 16384)["gated_delta"]
    least = flops.roofline_seconds(
        {k: v * steps for k, v in work.items()}, peak
    )["seconds"]
    names = ("gdn_chunk_wy_fwd", "gdn_chunk_read_fwd", "gdn_chunk_wy_bwd",
             "gdn_chunk_read_bwd")
    ops = [
        {"name": f"%{name}.{i}", "count": steps * 3,
         "about": "custom-call custom_call_target=\"tpu_custom_call\"",
         "total_s": 0.25 * steps * 0.08}
        for i, name in enumerate(names)
    ] + [
        {"name": "%fusion.7", "about": "fusion", "count": steps,
         "total_s": 9.0},
        {"name": "%flash_attn_fwd.1", "count": steps, "total_s": 1.0,
         "about": "custom-call custom_call_target=\"tpu_custom_call\""},
    ]
    run = SimpleNamespace(
        trace={"devices": [{"steps": steps, "ops": ops}]}, peak=peak,
        hook=family, config=config, cell=cell,
    )
    got = reader.read(run)
    assert abs(got - 100.0 * least / (steps * 0.08)) < 1e-9
    assert 0.0 < got < reader.CEILING
    note = next(n for n in harness.json_lines(capsys.readouterr().out)
                if isinstance(n, dict) and "gated_delta_kernels" in n)
    assert note["steps_traced"] == steps
    assert note["roofline"]["bound"] == "bytes"
    # no such kernel in the trace (the plain path), no trace, or a family
    # that counts no such work: nothing, and no error
    plain = SimpleNamespace(
        trace={"devices": [{"steps": steps, "ops": ops[4:]}]}, peak=peak,
        hook=family, config=config, cell=cell,
    )
    assert reader.read(plain) is None
    assert reader.read(SimpleNamespace(trace=None, peak=peak)) is None
    import flops_qwen3_next

    qwen = _config("qwen3-next-80b-a3b-d4")
    other = SimpleNamespace(
        trace=run.trace, peak=peak, hook=flops_qwen3_next, config=qwen,
        cell=harness.load_cell("qwen3-next-80b-a3b-d4.steady"),
    )
    assert reader.read(other) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-olmo-hybrid.steady", seed=3000000064, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # G-G-G-*- at width 64: 3 DeltaNet mixers (30 heads of 4 / 8), 1
    # attention (2 heads of 32), 4 SwiGLUs of 96, a norm an entry
    deltanet = (
        64 * (120 + 120 + 240 + 240) + 64 * 60 + 240 * 64 + 480 * 4 + 60 + 8
    )
    attention = 4 * 64 * 64 + 2 * 64
    assert notes["n_params"] == (
        2 * 256 * 64 + 64 + 3 * deltanet + attention + 4 * 3 * 64 * 96
        + 8 * 64
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-olmo-hybrid.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-olmo-hybrid.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    pipeline = window["pipeline"]
    # three DeltaNet mixers over 64 tokens in chunks of 16, forward and
    # backward; recomputed layers that keep the pass's results
    assert mods["gdn.serial_chunk_steps"].read(run) == 3 * 4 * 2
    assert pipeline["gdn_sites"] == 6 and pipeline["gdn_kept_sites"] == 3
    assert pipeline["gdn_beta_scaled_sites"] == 3
    assert pipeline["reordered_norm_sites"] == 2
    # heads of 4 / 8 are no quarter tiles: the plain statement, no lanes
    assert pipeline["gdn_kernel_sites"] == 0
    assert pipeline["gdn_head_lanes"] == 0
    assert mods["gdn.head_lanes_used_pct"].read(run) is None
