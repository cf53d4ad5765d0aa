"""The benchmark's tests of what the Nemotron-H configuration brought
(PR 37), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_nemotron_h.py`` against numbers worked by hand (each layer kind
counted once a layer of its kind, held experts only, the attention layer at
its own head count), the configuration file against the source, the new
reader on hand-made runs, and one CPU rehearsal of a hybrid cell through the
whole chain at a toy size. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_nemotron_h as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "nemotron3-nano-30b-a3b-d9"


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one layer of each kind, by hand, at the published widths
MAMBA = (
    2688 * (4096 + 4096 + 2 * 8 * 128 + 64)  # in-projection [z|xBC|dt]
    + 4096 * 2688  # out-projection
    + 6144 * 4 + 6144  # convolution of 4 over x, B, C and its bias
    + 3 * 64  # dt_bias, A_log, D
    + 4096 + 2688  # the gate's norm weight, the layer's norm
)
ATTENTION = 2 * 2688 * 32 * 128 + 2 * 2688 * 2 * 128 + 2688
SPARSE = 2688 * 128 + 128 + 2 * 2688 * 3712 + 2688  # router, b, shared
EXPERT = 2 * 2688 * 1856
TABLES = 2 * 16384 * 2688 + 2688


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (p["M"], p["*"], p["E"], p["expert"]) == (
        MAMBA, ATTENTION, SPARSE, EXPERT
    )
    assert (MAMBA, ATTENTION, SPARSE, EXPERT) == (
        38744896, 23399040, 20302592, 9977856
    )
    c = family.count(m, 8192)
    # MEMEM*EME: 4 Mamba-2, 4 expert layers of 8 held experts, 1 attention
    held = TABLES + 4 * MAMBA + ATTENTION + 4 * SPARSE + 4 * 8 * EXPERT
    assert c["params"] == held == 666963456
    # a token's 6 assignments fall on the 8 of 128 held 6 * 8 / 128 times
    assert c["active_params"] == held - 4 * 8 * EXPERT + 4 * 0.375 * EXPERT
    # the whole model, every expert and the whole vocabulary held: 31.6 B
    pub = _config()["published"]
    whole = dict(
        m, layer_pattern=pub["hybrid_override_pattern"],
        num_layers=pub["num_hidden_layers"], vocab_size=pub["vocab_size"],
        experts_held=pub["n_routed_experts"],
    )
    full = family.count(whole, 8192)
    assert abs(full["params"] / 1e9 - 31.6) < 0.05
    assert abs(full["active_params"] / 1e9 - 3.6) < 0.05  # tables included


def test_operations_a_token_by_hand():
    m = _config()["model"]
    c = family.count(m, 8192)
    scan = 128 * (8 * 128 + 64 * 64) + 4 * 64 * 64 * 128
    assert family.scan_flops_per_token(m) == scan == 2752512
    by_kind = c["by_kind"]
    assert by_kind["M"] == 4 * (
        6 * (2688 * 10304 + 4096 * 2688) + 3 * scan
    )
    # the one attention layer: 32 heads of 128, not 2688 / 32
    assert by_kind["*"] == 6 * (
        2 * 2688 * 4096 + 2 * 2688 * 256
    ) + 12 * 8192 * 4096 / 2
    assert by_kind["E"] == 4 * 6 * (
        2688 * 128 + 2 * 2688 * 3712 + 0.375 * EXPERT
    )
    assert by_kind["head"] == 6 * 2688 * 16384
    assert c["train_flops_per_token"] == sum(by_kind.values())
    assert abs(c["train_flops_per_token"] / 1e9 - 2.145) < 0.001
    share = {k: v / c["train_flops_per_token"] for k, v in by_kind.items()}
    assert abs(share["M"] - 0.448) < 0.001
    assert abs(share["head"] - 0.123) < 0.001


def test_step_work_counts_each_kind_once_a_layer_of_its_kind():
    m = _config()["model"]
    w = family.step_work(m, 1, 8192)
    assert set(w) == {"attention", "grouped_matmul", "ssm_scan"}
    # ONE attention layer at 32 heads of 128: 6 matmuls of 2 T^2 D a
    # head, halved by the mask; k and v at 2 heads in the bytes
    one = flops.attention_kernel_work(1, 32, 8192, 128)
    assert w["attention"]["flops"] == one["flops"] == (
        6 * 2 * 8192**2 * 128 * 0.5 * 32
    )
    assert w["attention"]["bytes"] == (5 * 32 + 6 * 2) * 8192 * 128 * 2
    # FOUR expert layers; 8192 * 6 * 8 / 128 = 3072 rows through the 2
    # projections of the 8 held matrices, forward + backward
    rows = 3072
    assert family.held_rows(m, 8192) == rows
    assert w["grouped_matmul"]["flops"] == 4 * 2 * 3 * 2 * rows * 2688 * 1856
    assert w["grouped_matmul"]["bytes"] == 4 * 2 * 3 * 2 * (
        rows * 2688 + rows * 1856 + 8 * 2688 * 1856
    )
    # FOUR Mamba-2 layers' scans
    assert w["ssm_scan"]["flops"] == 4 * 3 * 2752512 * 8192
    moved = (4096 + 2048) * 2 + 4 * 64
    assert w["ssm_scan"]["bytes"] == 4 * 8192 * (
        (moved + 8192) + (moved + 8192 + moved)
    )
    # a pattern without a kind runs no such kernel
    none = family.step_work(dict(m, layer_pattern="MMM", num_layers=3), 1, 8192)
    assert none["attention"] is None and none["grouped_matmul"] is None
    # and a pattern that is not the layers is refused, not guessed
    for bad in ({"num_layers": 8}, {"layer_pattern": "MEMEM-EME"}):
        try:
            family.count(dict(m, **bad), 8192)
        except ValueError:
            continue
        raise AssertionError(bad)


def test_the_file_holds_the_source_and_only_the_cut_differs():
    c = _config()
    m, pub = c["model"], c["published"]
    cut = {"num_hidden_layers", "hybrid_override_pattern", "vocab_size"}
    for key, value in pub.items():
        assert (c[key] == value) == (key not in cut), key
    assert set(c["reduced"]) == cut | {
        "num_layers", "layer_pattern", "experts_held"
    }
    # the first nine layers of the published pattern, a whole period
    assert m["layer_pattern"] == pub["hybrid_override_pattern"][:9]
    assert c["hybrid_override_pattern"] == m["layer_pattern"] == "MEMEM*EME"
    assert (c["num_hidden_layers"], m["num_layers"]) == (9, 9)
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["num_kv_heads"],
        m["attn_head_dim"], m["ssm_heads"], m["ssm_head_dim"],
        m["ssm_state"], m["ssm_groups"], m["ssm_conv"], m["ssm_chunk"],
        m["mlp_dim"], m["shared_expert_dim"], m["moe_top_k"],
        m["num_experts"], m["routed_scale"], m["norm_eps"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"],
        pub["mamba_num_heads"], pub["mamba_head_dim"],
        pub["ssm_state_size"], pub["n_groups"], pub["conv_kernel"],
        pub["chunk_size"], pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"],
        pub["num_experts_per_tok"], pub["n_routed_experts"],
        pub["routed_scaling_factor"], pub["norm_eps"],
    )
    # the inner width is heads x head width (4096); the source's
    # ``expand`` of 2 would say 5376 and its modelling code does not use it
    assert m["ssm_heads"] * m["ssm_head_dim"] == 4096
    # the floors: a whole period, 8 experts, an eighth of the vocabulary
    assert m["experts_held"] == 8 and m["vocab_size"] * 8 == pub["vocab_size"]
    assert c["arithmetic"]["parameters"] == family.count(m, 8192)["params"]


def test_held_share_reader():
    mods = harness.load_layer_metrics()
    mod = mods["moe.held_share_pct"]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        "step program", "%", "tokens_per_s"
    )
    config = _config()

    def run(opened, closed, config=config):
        return SimpleNamespace(
            config=config,
            window={"pipeline_open": opened, "pipeline": closed},
        )

    opened = {"moe_reports": 10, "moe_held_share_sum": 0.7}
    closed = {"moe_reports": 14, "moe_held_share_sum": 0.95}
    assert abs(mod.read(run(opened, closed)) - 6.25) < 1e-9
    # a program without the counter (the parent's), no report in the
    # window, a configuration that holds every expert: nothing
    assert mod.read(run({"moe_reports": 10}, {"moe_reports": 14})) is None
    assert mod.read(run(closed, closed)) is None
    assert mod.read(run(opened, closed, _config("olmoe-1b-7b-d2"))) is None
    assert mod.read(run({}, {})) is None
    cells = {
        n: harness.load_cell(n) for n in (
            "nemotron3-nano-30b-a3b-d9.steady", "olmoe-1b-7b-d2.steady",
            "gpt2-124m.steady",
        )
    }
    assert [n for n, c in cells.items() if mod.CELLS(c)] == [
        "nemotron3-nano-30b-a3b-d9.steady"
    ]


def test_cpu_rehearsal_of_a_hybrid_cell(capsys):
    res = harness.run_cell(
        "toy-nemotron.steady", seed=3000000023, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # MEM*E at width 64: 2 Mamba-2 (8 heads of 8, 8 groups of 16),
    # 1 attention (4 / 2 heads of 32), 2 layers of 4 held experts of 32
    mamba = 64 * (64 + 64 + 256 + 8) + 64 * 64 + 320 * 5 + 24 + 64 + 64
    attention = 2 * 64 * 128 + 2 * 64 * 64 + 64
    sparse = 64 * 16 + 16 + 2 * 64 * 48 + 64
    assert notes["n_params"] == (
        2 * 256 * 64 + 64 + 2 * mamba + attention
        + 2 * (sparse + 4 * 2 * 64 * 32)
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-nemotron.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-nemotron.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # 4 of 16 experts held: a quarter of the assignments, more or less
    assert 10.0 < mods["moe.held_share_pct"].read(run) < 45.0
    assert mods["moe.max_expert_load"].read(run) >= 1.0
