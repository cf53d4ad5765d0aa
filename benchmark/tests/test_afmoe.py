"""The benchmark's tests of what the Trinity-Mini configuration brought
(PR 50), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_afmoe.py`` against numbers worked by hand (each layer kind counted
once a layer of its kind at its own widths, the window layers at the pairs
a window sees, held experts only), the configuration file against the
source, and the two new readers on hand-made runs. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_afmoe as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "trinity-mini-d5"
CELL = f"{NAME}.steady"
T, W = 16384, 2048


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one layer of each kind, by hand, at the published widths
ATTN = (
    2048 * 32 * 256  # q_proj and the gate's projection: a head's [q | g]
    + 2 * 2048 * 4 * 128  # k_proj, v_proj: 4 key/value heads
    + 32 * 128 * 2048  # o_proj
    + 2 * 128 + 2 * 2048  # the two head norms, the layer's two norms
)
DENSE = 3 * 2048 * 6144 + 2 * 2048
SPARSE = 2048 * 128 + 128 + 3 * 2048 * 1024 + 2 * 2048  # router, bias, shared
EXPERT = 3 * 2048 * 1024
TABLES = 2 * 25024 * 2048 + 2048
# pairs a head sees of one row: through the window, and causally
SEEN = T * W - W * (W - 1) // 2
HALF = T * T // 2


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (p["W"], p["*"], p["-"], p["E"], p["expert"]) == (
        ATTN, ATTN, DENSE, SPARSE, EXPERT
    )
    assert (ATTN, DENSE, SPARSE, EXPERT) == (
        27267328, 37752832, 6557824, 6291456
    )
    c = family.count(m, T)
    # W-WE*EWEWE: 5 attention layers, 1 dense, 4 expert blocks of 16 held
    held = TABLES + 5 * ATTN + DENSE + 4 * SPARSE + 4 * 16 * EXPERT
    assert c["params"] == held == 705474304
    # a token's 8 assignments fall on the 16 of 128 held once
    assert c["active_params"] == held - 4 * 16 * EXPERT + 4 * 1.0 * EXPERT
    # the whole model, every expert and the whole vocabulary held: 26B-A3B
    pub = _config()["published"]
    pattern = "".join(
        ("W" if kind == "sliding_attention" else "*")
        + ("-" if i < pub["num_dense_layers"] else "E")
        for i, kind in enumerate(pub["layer_types"])
    )
    assert pattern == _config()["reduced_from"]["layer_pattern"][0]
    assert pattern[2:12] == m["layer_pattern"]  # published layers 1-5
    whole = dict(
        m, layer_pattern=pattern, num_layers=len(pattern),
        vocab_size=pub["vocab_size"], experts_held=pub["num_experts"],
    )
    full = family.count(whole, T)
    assert abs(full["params"] / 1e9 - 26.12) < 0.01
    assert abs(full["active_params"] / 1e9 - 3.47) < 0.01  # tables included
    # two periods (published layers 1-9) would not fit: 1,243 M held
    two = dict(m, layer_pattern="W-WE*EWEWEWEWE*EWE", num_layers=18)
    assert abs(family.count(two, T)["params"] / 1e6 - 1243.4) < 0.1


def test_operations_a_token_by_hand():
    m = _config()["model"]
    c = family.count(m, T)
    assert (SEEN, HALF) == (31458304, 134217728)
    assert family.visible_pairs(T, W) == SEEN
    assert family.visible_pairs(T, 0) == HALF
    # a window as long as the row sees the causal pairs, diagonal included
    assert family.visible_pairs(64, 64) == family.visible_pairs(64, 99)
    assert family.visible_pairs(64, 64) == 64 * 65 / 2
    mm = 2048 * 32 * 256 + 2 * 2048 * 512 + 4096 * 2048
    window_scores = 2 * 2 * 32 * 128 * SEEN / T
    causal_scores = 2 * 2 * 32 * 128 * HALF / T
    assert family.attention_flops_per_token(m, T, W) == window_scores
    assert family.attention_flops_per_token(m, T, 0) == causal_scores
    by_kind = c["by_kind"]
    assert by_kind["W"] == 4 * (6 * mm + 3 * window_scores)
    assert by_kind["*"] == 6 * mm + 3 * causal_scores
    assert by_kind["-"] == 6 * 3 * 2048 * 6144
    assert by_kind["E"] == 4 * 6 * (
        2048 * 128 + 3 * 2048 * 1024 + 1.0 * EXPERT
    )
    assert by_kind["head"] == 6 * 2048 * 25024
    assert c["train_flops_per_token"] == sum(by_kind.values())
    # 39.99 TFLOP a step of 16384 tokens, 12.78 of them the attention's
    # scores and values (32.99 of 60.19 were the window not walked)
    assert abs(c["train_flops_per_token"] * T / 1e12 - 39.99) < 0.01
    scores = 3 * T * (4 * window_scores + causal_scores)
    assert abs(scores / 1e12 - 12.78) < 0.01
    unwalked = 3 * T * 5 * causal_scores
    assert abs(unwalked / 1e12 - 32.99) < 0.01
    step = c["train_flops_per_token"] * T
    assert abs((step - scores + unwalked) / 1e12 - 60.19) < 0.01


def test_step_work_counts_each_kind_once_a_layer_of_its_kind():
    m = _config()["model"]
    w = family.step_work(m, 1, T)
    assert set(w) == {"attention", "attention_window", "grouped_matmul"}
    causal = flops.attention_kernel_work(1, 32, T, 128)
    assert causal["flops"] == 6 * 2 * HALF * 128 * 32
    # FOUR window layers over the pairs a window sees: six matmuls of
    # 2 * pairs * 128 a head, 32 heads; the bytes of a causal layer
    assert w["attention_window"]["flops"] == 4 * 6 * 2 * SEEN * 128 * 32
    assert w["attention_window"]["bytes"] == 4 * causal["bytes"]
    assert causal["bytes"] == 11 * 32 * T * 128 * 2
    # ... plus ONE global layer as a causal one is counted
    assert w["attention"]["flops"] == (
        w["attention_window"]["flops"] + causal["flops"]
    )
    assert w["attention"]["bytes"] == 5 * causal["bytes"]
    assert abs(w["attention"]["flops"] / 1e12 - 12.78) < 0.01
    assert abs(SEEN / HALF - 0.2344) < 0.0001
    # FOUR expert blocks; 16384 * 8 * 16 / 128 = 16384 rows through the
    # 3 projections of the 16 held matrices (1,024 rows a held expert)
    rows = 16384
    assert family.held_rows(m, T) == rows
    assert w["grouped_matmul"]["flops"] == 4 * 3 * 3 * 2 * rows * 2048 * 1024
    assert w["grouped_matmul"]["bytes"] == 4 * 3 * 3 * 2 * (
        rows * 2048 + rows * 1024 + 16 * 2048 * 1024
    )
    # a pattern without a kind runs no such kernel
    none = family.step_work(dict(m, layer_pattern="*-", num_layers=2,
                                 attn_window=0), 1, T)
    assert none["attention_window"] is None
    assert none["grouped_matmul"] is None
    assert none["attention"] == causal
    assert family.step_work(
        dict(m, layer_pattern="-E", num_layers=2, attn_window=0), 1, T
    )["attention"] is None
    # and a pattern that is not the layers is refused, not guessed
    for bad in ({"num_layers": 7}, {"layer_pattern": "W-WEGEWEWE"},
                {"attn_window": 0}):
        try:
            family.count(dict(m, **bad), T)
        except ValueError:
            continue
        raise AssertionError(bad)


def test_the_file_holds_the_source_and_only_the_cut_differs():
    c = _config()
    m, pub = c["model"], c["published"]
    cut = {"num_hidden_layers", "num_dense_layers", "layer_types",
           "vocab_size"}
    for key, value in pub.items():
        assert (c[key] == value) == (key not in cut), key
    assert set(c["reduced"]) == cut | {
        "num_layers", "layer_pattern", "experts_held"
    }
    assert set(c["reduced_from"]) == set(c["reduced"])
    for key, (published, here) in c["reduced_from"].items():
        if key in pub and key != "layer_types":
            assert (pub[key], c[key]) == (published, here), key
    assert c["vocab_size"] == m["vocab_size"]
    assert c["source"].endswith("arcee-ai/Trinity-Mini/blob/main/config.json")
    # one leading dense layer, then a whole period: published layers 1-5
    assert pub["global_attn_every_n_layers"] == 4
    assert c["layer_types"] == pub["layer_types"][1:6]
    assert c["layer_types"].count("full_attention") == 1
    assert m["layer_pattern"] == "W-WE*EWEWE"
    assert (c["num_hidden_layers"], c["num_dense_layers"]) == (5, 1)
    assert m["num_layers"] == 2 * c["num_hidden_layers"]
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["num_kv_heads"],
        m["attn_head_dim"], m["attn_window"], m["rope_theta"],
        m["dense_mlp_dim"], m["mlp_dim"], m["shared_expert_dim"],
        m["moe_top_k"], m["num_experts"], m["routed_scale"], m["norm_eps"],
        m["norm_topk_prob"], m["tie_embeddings"], m["router"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["sliding_window"],
        pub["rope_theta"], pub["intermediate_size"],
        pub["moe_intermediate_size"],
        pub["num_shared_experts"] * pub["moe_intermediate_size"],
        pub["num_experts_per_tok"], pub["num_experts"], pub["route_scale"],
        pub["rms_norm_eps"], pub["route_norm"], pub["tie_word_embeddings"],
        pub["score_func"],
    )
    assert pub["mup_enabled"] and m["embed_scale"] is True
    assert pub["n_group"] == pub["topk_group"] == 1  # no group limit
    assert "router_groups" not in m
    assert pub["rope_scaling"] is None and m["positions"] == "window"
    assert m["mixer_out_norm"] and m["attn_gate"] == "sigmoid"
    assert (m["qk_norm"], m["qk_norm_span"]) == (True, "head")
    # the floors: a whole period and four layers past the dense one, 16
    # >= 8 experts, an eighth of the vocabulary
    assert m["experts_held"] == 16 and c["num_experts"] == 128
    # an eighth of the vocabulary, not rounded up to whole lane tiles
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    assert m["vocab_size"] % 128 == 64
    assert c["arithmetic"]["parameters"] == family.count(m, T)["params"]
    for item in (
        "norms", "window_positions", "output_gate", "qk_norm",
        "router_bias", "embed_scale", "load_balance",
    ):
        assert item in c["assumed"], item
    assert "8 chips" in c["deployment"]
    # this router must keep fp32 moments
    assert 2048 * 128 < c["optimizer"]["min_quantized_size"]
    cell = harness.load_cell(CELL)
    assert (cell["batch"], cell["seq"], cell["chips"], cell["moe"]) == (
        1, T, 1, True
    )
    assert cell["seq"] == m["max_seq_len"]
    assert cell["trace"]["steps"] == 20 and not cell["kill"]


def test_the_window_readers():
    mods = harness.load_layer_metrics()
    walked = mods["attn.window_blocks_walked_pct"]
    roof = mods["kernel.attn_window_roofline"]
    for mod in (walked, roof):
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            "kernels", "%", "tokens_per_s"
        )
    assert roof.CEILING == 100.0 and not hasattr(walked, "CEILING")
    config = _config()

    def run(closed, config=config):
        return SimpleNamespace(config=config, window={"pipeline": closed})

    # four window layers' forward and one-pass backward, blocks of 1024
    assert walked.read(run({
        "attn_window_blocks_walked": 8 * 45,
        "attn_window_blocks_causal": 8 * 136,
    })) == 100.0 * 45 / 136
    assert round(100.0 * 150 / 528, 1) == 28.4  # blocks of 512
    # the window as a mask
    assert walked.read(run({
        "attn_window_blocks_walked": 12 * 528,
        "attn_window_blocks_causal": 12 * 528,
    })) == 100.0
    # a program without the counters (the parent's), a step that was not
    # traced, a configuration without a window: nothing
    assert walked.read(run({"moe_reports": 14})) is None
    assert walked.read(run({"attn_window_blocks_causal": 0,
                            "attn_window_blocks_walked": 0})) is None
    assert walked.read(run({"attn_window_blocks_causal": 136})) is None
    assert walked.read(run({})) is None
    assert walked.read(SimpleNamespace(config=config, window={})) is None
    other = _config("nemotron3-nano-30b-a3b-d9")
    assert walked.read(run({
        "attn_window_blocks_walked": 45, "attn_window_blocks_causal": 136,
    }, other)) is None
    names = (
        CELL, "ling-3.0-flash-d7.steady", "qwen3-next-80b-a3b-d4.steady",
        "nemotron3-nano-30b-a3b-d9.steady", "olmoe-1b-7b-d2.steady",
        "gpt2-124m.steady",
    )
    for mod in (walked, roof):
        assert [mod.CELLS(harness.load_cell(n)) for n in names] == [
            True, False, False, False, False, False
        ]

    # the roofline reader on a hand-made trace: the band's kernels alone
    def op(name, seconds):
        return {"name": name, "about": "custom-call tpu_custom_call",
                "total_s": seconds, "count": 1}

    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    work = family.step_work(config["model"], 1, T)["attention_window"]
    least = work["flops"] / peak["bf16_flops"]  # bound by operations
    assert least > work["bytes"] / peak["hbm_bytes_per_s"]
    device = {"steps": 2, "ops": [
        op("%flash_attn_window_fwd.3", 2 * least),
        op("%flash_attn_window_bwd.4", 2 * least),
        op("%flash_attn_fwd.5", 1.0),  # the global layer's: not the band's
        op("%fusion.7", 5.0),
    ]}
    traced = SimpleNamespace(
        config=config, cell=harness.load_cell(CELL), hook=family, peak=peak,
        trace={"devices": [device]},
    )
    assert abs(roof.read(traced) - 50.0) < 1e-9
    # no band kernel in the trace (the parent's program, or a window run
    # as a mask), no trace, a family module that counts no such work
    device["ops"] = device["ops"][2:]
    assert roof.read(traced) is None
    traced.trace = None
    assert roof.read(traced) is None
    traced.trace = {"devices": [device]}
    traced.hook = SimpleNamespace(step_work=lambda *a: {"attention": work})
    assert roof.read(traced) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-afmoe.steady", seed=3000000050, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # W-WE*EWEWE at width 64: 5 attention layers (4 heads on 2 of 16, the
    # query projection twice as wide), a dense layer of 96, 4 blocks of 8
    # held experts of 32 beside a shared one of 32; two norms a layer
    attn = 64 * 4 * 32 + 2 * 64 * 2 * 16 + 4 * 16 * 64 + 2 * 16 + 2 * 64
    dense = 3 * 64 * 96 + 2 * 64
    sparse = 64 * 64 + 64 + 3 * 64 * 32 + 2 * 64
    assert notes["n_params"] == (
        2 * 256 * 64 + 64 + 5 * attn + dense
        + 4 * (sparse + 8 * 3 * 64 * 32)
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-afmoe.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-afmoe.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    # on the CPU the attention is the jnp path: no kernel site is counted
    assert mods["attn.window_blocks_walked_pct"].read(run) is None
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # 8 of 64 experts held: an eighth of the assignments, more or less
    assert 4.0 < mods["moe.held_share_pct"].read(run) < 30.0
    assert mods["moe.max_expert_load"].read(run) >= 1.0
