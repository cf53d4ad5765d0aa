"""The benchmark's tests of what the Qwen3-Next configuration brought
(PR 43), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_qwen3_next.py`` against numbers worked by hand (each mixer kind
counted once a layer of its kind at its own widths, held experts only, the
attention layer at heads of 256), the configuration file against the
source, the new reader on hand-made runs, and one CPU rehearsal of the cell
through the whole chain at a toy size. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_qwen3_next as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "qwen3-next-80b-a3b-d4"


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one layer of each kind, by hand, at the published widths
DELTANET = (
    2048 * (2048 + 2048 + 4096 + 4096)  # in_proj_qkvz [q | k | v | z]
    + 2048 * 64  # in_proj_ba
    + 4096 * 2048  # out-projection
    + 8192 * 4  # convolution of 4 over q, k, v, no bias
    + 2 * 32  # dt_bias, A_log
    + 128 + 2048  # the gated norm's weight, the layer's norm
)
ATTENTION = (
    2048 * 16 * 512  # q_proj: a head's query and its gate
    + 2 * 2048 * 2 * 256 + 16 * 256 * 2048  # k, v, o
    + 2 * 256 + 2048  # the two head norms, the layer's norm
)
SPARSE = 2048 * 512 + 3 * 2048 * 512 + 2048 + 2048  # router, shared, gate
EXPERT = 3 * 2048 * 512
TABLES = 2 * 19072 * 2048 + 2048


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (p["G"], p["*"], p["E"], p["expert"]) == (
        DELTANET, ATTENTION, SPARSE, EXPERT
    )
    assert (DELTANET, ATTENTION, SPARSE, EXPERT) == (
        33720512, 27265536, 4198400, 3145728
    )
    c = family.count(m, 8192)
    # GEGEGE*E: 3 DeltaNet, 1 attention, 4 expert blocks of 32 held
    held = TABLES + 3 * DELTANET + ATTENTION + 4 * SPARSE + 4 * 32 * EXPERT
    assert c["params"] == held == 625994816
    # a token's 10 assignments fall on the 32 of 512 held 0.625 times
    assert c["active_params"] == held - 4 * 32 * EXPERT + 4 * 0.625 * EXPERT
    # the whole model, every expert and the whole vocabulary held: 79.7 B
    pub = _config()["published"]
    whole = dict(
        m, layer_pattern=m["layer_pattern"] * 12,
        num_layers=2 * pub["num_hidden_layers"],
        vocab_size=pub["vocab_size"], experts_held=pub["num_experts"],
    )
    full = family.count(whole, 8192)
    assert abs(full["params"] / 1e9 - 79.7) < 0.05
    assert abs(full["active_params"] / 1e9 - 3.9) < 0.05  # tables included


def test_operations_a_token_by_hand():
    m = _config()["model"]
    c = family.count(m, 8192)
    # a key head's two score halves; a value head's triangle inverse,
    # three triangle products and three products with the state
    scan = 16 * 2 * 64 * 128 + 32 * (
        2 * 64 * 64 / 3 + 64 * (128 + 128 + 128) + 6 * 128 * 128
    )
    assert family.scan_flops_per_token(m) == scan
    assert abs(scan - 4281685) < 1
    by_kind = c["by_kind"]
    assert by_kind["G"] == 3 * (
        6 * (2048 * 12288 + 2048 * 64 + 4096 * 2048) + 3 * scan
    )
    # the one attention layer: 16 heads of 256, not 2048 / 16
    assert by_kind["*"] == 6 * (
        2048 * 8192 + 2 * 2048 * 512 + 4096 * 2048
    ) + 12 * 8192 * 4096 / 2
    assert by_kind["E"] == 4 * 6 * (
        2048 * 512 + 3 * 2048 * 512 + 2048 + 0.625 * EXPERT
    )
    assert by_kind["head"] == 6 * 2048 * 19072
    assert c["train_flops_per_token"] == sum(by_kind.values())
    assert abs(c["train_flops_per_token"] / 1e9 - 1.392) < 0.001
    share = {k: v / c["train_flops_per_token"] for k, v in by_kind.items()}
    assert abs(share["G"] - 0.463) < 0.001
    assert abs(share["*"] - 0.262) < 0.001
    assert abs(share["head"] - 0.168) < 0.001


def test_step_work_counts_each_kind_once_a_layer_of_its_kind():
    m = _config()["model"]
    w = family.step_work(m, 1, 8192)
    assert set(w) == {"attention", "grouped_matmul", "gdn_scan"}
    # ONE attention layer at 16 heads of 256: 6 matmuls of 2 T^2 D a
    # head, halved by the mask; k and v at 2 heads in the bytes
    one = flops.attention_kernel_work(1, 16, 8192, 256)
    assert w["attention"]["flops"] == one["flops"] == (
        6 * 2 * 8192**2 * 256 * 0.5 * 16
    )
    assert w["attention"]["bytes"] == (5 * 16 + 6 * 2) * 8192 * 256 * 2
    # FOUR expert blocks; 8192 * 10 * 32 / 512 = 5120 rows through the 3
    # projections of the 32 held matrices, forward + backward
    rows = 5120
    assert family.held_rows(m, 8192) == rows
    assert w["grouped_matmul"]["flops"] == 4 * 3 * 3 * 2 * rows * 2048 * 512
    assert w["grouped_matmul"]["bytes"] == 4 * 3 * 3 * 2 * (
        rows * 2048 + rows * 512 + 32 * 2048 * 512
    )
    # THREE DeltaNet layers' scans
    assert w["gdn_scan"]["flops"] == 3 * 3 * family.scan_flops_per_token(m) * 8192
    moved = 8192 * 2 + 2 * 4 * 32  # q, k, v; beta and g in float32
    assert w["gdn_scan"]["bytes"] == 3 * 8192 * (
        (moved + 8192) + (moved + 8192 + moved)
    )
    # a pattern without a kind runs no such kernel
    none = family.step_work(dict(m, layer_pattern="GGG", num_layers=3), 1, 8192)
    assert none["attention"] is None and none["grouped_matmul"] is None
    # and a pattern that is not the layers is refused, not guessed
    for bad in ({"num_layers": 7}, {"layer_pattern": "GEGEGEME"}):
        try:
            family.count(dict(m, **bad), 8192)
        except ValueError:
            continue
        raise AssertionError(bad)


def test_the_file_holds_the_source_and_only_the_cut_differs():
    c = _config()
    m, pub = c["model"], c["published"]
    cut = {"num_hidden_layers", "num_experts", "vocab_size"}
    for key, value in pub.items():
        assert (c[key] == value) == (key not in cut), key
    assert set(c["reduced"]) == cut | {
        "num_layers", "layer_pattern", "experts_held"
    }
    assert set(c["reduced_from"]) == set(c["reduced"])
    # one whole period: three linear-attention layers to one full one,
    # each published layer the program's mixer entry and expert entry
    assert pub["full_attention_interval"] == 4
    assert m["layer_pattern"] == "GEGEGE*E"
    assert (c["num_hidden_layers"], m["num_layers"]) == (4, 8)
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["num_kv_heads"],
        m["attn_head_dim"], m["gdn_value_heads"], m["gdn_key_heads"],
        m["gdn_key_dim"], m["gdn_value_dim"], m["gdn_conv"],
        m["mlp_dim"], m["shared_expert_dim"], m["moe_top_k"],
        m["num_experts"], m["norm_eps"], m["rope_theta"],
        m["norm_topk_prob"], m["tie_embeddings"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["linear_num_value_heads"], pub["linear_num_key_heads"],
        pub["linear_key_head_dim"], pub["linear_value_head_dim"],
        pub["linear_conv_kernel_dim"], pub["moe_intermediate_size"],
        pub["shared_expert_intermediate_size"], pub["num_experts_per_tok"],
        pub["num_experts"], pub["rms_norm_eps"], pub["rope_theta"],
        pub["norm_topk_prob"], pub["tie_word_embeddings"],
    )
    assert m["rope_dim"] == pub["partial_rotary_factor"] * pub["head_dim"]
    assert m["gdn_chunk"] == 64  # the source's own chunk
    # the floors: a whole period, 32 >= 8 experts, an eighth of the
    # vocabulary (of the table padded to 152576: 149 whole lane tiles)
    assert (c["num_experts"], m["experts_held"]) == (32, 32)
    assert m["vocab_size"] * 8 == 152576 >= pub["vocab_size"]
    assert m["vocab_size"] % 128 == 0
    assert c["arithmetic"]["parameters"] == family.count(m, 8192)["params"]
    # this router must keep fp32 moments
    assert 2048 * 512 < c["optimizer"]["min_quantized_size"] <= 2 * 2048 * 512


def test_serial_chunk_steps_reader():
    mods = harness.load_layer_metrics()
    mod = mods["gdn.serial_chunk_steps"]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        "step program", "steps", "tokens_per_s"
    )
    config = _config()

    def run(closed, config=config):
        return SimpleNamespace(config=config, window={"pipeline": closed})

    assert mod.read(run({"gdn_sites": 3, "gdn_chunk_steps": 768})) == 768.0
    # a program without the counter (the parent's), a step that was not
    # traced, a configuration without the layer kind: nothing
    assert mod.read(run({"moe_reports": 14})) is None
    assert mod.read(run({"gdn_sites": 0, "gdn_chunk_steps": 0})) is None
    assert mod.read(run({})) is None
    assert mod.read(SimpleNamespace(config=config, window={})) is None
    assert mod.read(
        run({"gdn_chunk_steps": 768}, _config("nemotron3-nano-30b-a3b-d9"))
    ) is None
    cells = {
        n: harness.load_cell(n) for n in (
            "qwen3-next-80b-a3b-d4.steady",
            "nemotron3-nano-30b-a3b-d9.steady", "olmoe-1b-7b-d2.steady",
            "gpt2-124m.steady",
        )
    }
    assert [n for n, c in cells.items() if mod.CELLS(c)] == [
        "qwen3-next-80b-a3b-d4.steady"
    ]
    # the readers that take the cell through their own rules
    for name in ("moe.held_share_pct", "moe.drop_rate_pct",
                 "moe.max_expert_load", "kernel.moe_gmm_roofline",
                 "opt.q8_tiles_share", "kernel.attn_roofline",
                 "step.device_ms"):
        assert mods[name].CELLS(cells["qwen3-next-80b-a3b-d4.steady"]), name


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-qwen3-next.steady", seed=3000000043, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # GEGE*E at width 64: 2 DeltaNet (32 / 16 heads of 4), 1 attention
    # (2 / 1 heads of 128, gated), 3 blocks of 8 held experts of 32
    deltanet = (
        64 * (64 + 64 + 128 + 128) + 64 * 64 + 128 * 64 + 256 * 4 + 64
        + 4 + 64
    )
    attention = 64 * 2 * 256 + 2 * 64 * 128 + 256 * 64 + 2 * 128 + 64
    sparse = 64 * 32 + 3 * 64 * 48 + 64 + 64
    assert notes["n_params"] == (
        2 * 256 * 64 + 64 + 2 * deltanet + attention
        + 3 * (sparse + 8 * 3 * 64 * 32)
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-qwen3-next.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-qwen3-next.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    # two DeltaNet mixers over 64 tokens in chunks of 16, forward and
    # backward: the train step's, not the reference check's forward pass
    assert mods["gdn.serial_chunk_steps"].read(run) == 2 * 4 * 2
    assert window["pipeline"]["gdn_sites"] == 2
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # 8 of 32 experts held: a quarter of the assignments, more or less
    assert 10.0 < mods["moe.held_share_pct"].read(run) < 45.0
    assert mods["moe.max_expert_load"].read(run) >= 1.0
