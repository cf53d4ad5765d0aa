"""The benchmark's tests of what the Ling-3.0-flash configuration brought
(PR 45), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_ling3.py`` against numbers worked by hand (each mixer kind counted
once a layer of its kind at its own widths, held experts only, the latent
attention at 192 / 128), the configuration file against the source, the
new reader on hand-made runs, and one CPU rehearsal of the cell through the
whole chain at a toy size. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops_ling3 as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "ling-3.0-flash-d7"
CELL = f"{NAME}.steady"


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one layer of each kind, by hand, at the published widths
KDA = (
    3 * 2560 * 4096  # q, k, v projections
    + 3 * 4096 * 4  # three convolutions of 4, no bias
    + 2560 * 4096 + 32 + 4096  # the decay's projection, A_log, dt_bias
    + 2 * 2560 * 32  # beta and the head-wise gate
    + 4096 * 2560  # out-projection
    + 128 + 2560  # the gated norm's weight, the layer's norm
)
MLA = (
    2560 * 32 * 192  # q_proj: a head's [nope 128 | rope 64]
    + 2560 * (512 + 64) + 512  # kv_a, the latent's norm
    + 512 * 32 * (128 + 128)  # kv_b: a head's [k_nope | v]
    + 32 * 128 * 2560  # o_proj
    + 2 * 192 + 2560  # the two head norms, the layer's norm
)
DENSE = 3 * 2560 * 6144 + 2560
SPARSE = 2560 * 512 + 512 + 3 * 2560 * 768 + 2560  # router, bias, shared
EXPERT = 3 * 2560 * 768
TABLES = 2 * 19712 * 2560 + 2560


def test_parameters_by_hand():
    m = _config()["model"]
    p = family.layer_params(m)
    assert (p["G"], p["*"], p["-"], p["E"], p["expert"]) == (
        KDA, MLA, DENSE, SPARSE, EXPERT
    )
    assert (KDA, MLA, DENSE, SPARSE, EXPERT) == (
        52648608, 31886720, 47188480, 7212032, 5898240
    )
    c = family.count(m, 8192)
    # G-GEGEGE*EGEGE: 6 KDA, 1 MLA, 1 dense, 6 expert blocks of 8 held
    held = TABLES + 6 * KDA + MLA + DENSE + 6 * SPARSE + 6 * 8 * EXPERT
    assert c["params"] == held == 822282560
    # a token's 8 assignments fall on the 8 of 512 held 0.125 times
    assert c["active_params"] == held - 6 * 8 * EXPERT + 6 * 0.125 * EXPERT
    # the whole model, every expert and the whole vocabulary held: 124 B
    pub = _config()["published"]
    pattern = "".join(
        ("*" if (i + 1) % 6 == 0 else "G") + ("-" if i < 2 else "E")
        for i in range(pub["num_hidden_layers"])
    )
    assert pattern == _config()["reduced_from"]["layer_pattern"][0]
    assert pattern[2:16] == m["layer_pattern"]  # published layers 1-7
    whole = dict(
        m, layer_pattern=pattern, num_layers=len(pattern),
        vocab_size=pub["vocab_size"], experts_held=pub["num_experts"],
    )
    full = family.count(whole, 8192)
    assert abs(full["params"] / 1e9 - 124.0) < 0.05
    assert abs(full["active_params"] / 1e9 - 5.14) < 0.05  # tables included


def test_operations_a_token_by_hand():
    m = _config()["model"]
    c = family.count(m, 8192)
    # a head: its two score halves, the triangle's inverse, three
    # triangle products and three products with the state
    scan = 32 * (
        2 * 64 * 128 + 2 * 64 * 64 / 3 + 64 * (128 + 128 + 128)
        + 6 * 128 * 128
    )
    assert family.scan_flops_per_token(m) == scan
    assert abs(scan - 4543829) < 1
    by_kind = c["by_kind"]
    assert by_kind["G"] == 6 * (
        6 * (2560 * (3 * 4096 + 4096 + 64) + 4096 * 2560) + 3 * scan
    )
    # the one latent attention: scores 192 wide, values 128, 32 heads
    scores = 2 * 8192 * 32 * (192 + 128) / 2
    assert family.attention_flops_per_token(m, 8192) == scores == 83886080
    assert by_kind["*"] == 6 * (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560
    ) + 3 * scores
    assert by_kind["-"] == 6 * 3 * 2560 * 6144
    assert by_kind["E"] == 6 * 6 * (
        2560 * 512 + 3 * 2560 * 768 + 0.125 * EXPERT
    )
    assert by_kind["head"] == 6 * 2560 * 19712
    assert c["train_flops_per_token"] == sum(by_kind.values())
    assert abs(c["train_flops_per_token"] / 1e9 - 3.290) < 0.001
    share = {k: v / c["train_flops_per_token"] for k, v in by_kind.items()}
    assert abs(share["G"] - 0.600) < 0.001
    assert abs(share["*"] - 0.135) < 0.001
    assert abs(share["head"] - 0.092) < 0.001


def test_step_work_counts_each_kind_once_a_layer_of_its_kind():
    m = _config()["model"]
    w = family.step_work(m, 1, 8192)
    assert set(w) == {"attention", "grouped_matmul", "gdn_scan"}
    # ONE latent attention layer, 32 heads: three matmuls 192 wide (QK^T,
    # dQ, dK) and three 128 wide (PV, dV, dP), 2 T^2 each a head, halved
    # by the mask; nothing for the padding to 256
    assert w["attention"]["flops"] == (
        2 * 8192**2 * 0.5 * 32 * (3 * 192 + 3 * 128)
    )
    # q, k at 192 and v, o at 128 forward; q, k, v, do, dq, dk, dv back
    assert w["attention"]["bytes"] == 8192 * 32 * 2 * (
        (2 * 192 + 2 * 128) + (4 * 192 + 3 * 128)
    )
    # SIX expert blocks; 8192 * 8 * 8 / 512 = 1024 rows through the 3
    # projections of the 8 held matrices, forward + backward
    rows = 1024
    assert family.held_rows(m, 8192) == rows
    assert w["grouped_matmul"]["flops"] == 6 * 3 * 3 * 2 * rows * 2560 * 768
    assert w["grouped_matmul"]["bytes"] == 6 * 3 * 3 * 2 * (
        rows * 2560 + rows * 768 + 8 * 2560 * 768
    )
    # SIX KDA layers' scans; g is a float32 a head and key channel
    assert w["gdn_scan"]["flops"] == 6 * 3 * family.scan_flops_per_token(m) * 8192
    moved = 3 * 4096 * 2 + 4 * (32 + 4096)
    assert w["gdn_scan"]["bytes"] == 6 * 8192 * (
        (moved + 8192) + (moved + 8192 + moved)
    )
    # a pattern without a kind runs no such kernel
    none = family.step_work(dict(m, layer_pattern="G-G", num_layers=3), 1, 8192)
    assert none["attention"] is None and none["grouped_matmul"] is None
    # and a pattern that is not the layers is refused, not guessed
    for bad in ({"num_layers": 7}, {"layer_pattern": "G-GEGEGEMEGEGE"},
                {"gdn_key_heads": 16}):
        try:
            family.count(dict(m, **bad), 8192)
        except ValueError:
            continue
        raise AssertionError(bad)


def test_the_file_holds_the_source_and_only_the_cut_differs():
    c = _config()
    m, pub = c["model"], c["published"]
    cut = {
        "num_hidden_layers", "first_k_dense_replace", "num_experts",
        "vocab_size",
    }
    for key, value in pub.items():
        assert (c[key] == value) == (key not in cut), key
    assert set(c["reduced"]) == cut | {
        "num_layers", "layer_pattern", "experts_held"
    }
    assert set(c["reduced_from"]) == set(c["reduced"])
    # the leading dense layer once, then one whole period of six with the
    # full attention at (i + 1) mod 6 = 0: published layers 1-7
    assert pub["layer_group_size"] == 6 and pub["first_k_dense_replace"] == 2
    assert m["layer_pattern"] == "G-GEGEGE*EGEGE"
    assert (c["num_hidden_layers"], c["first_k_dense_replace"]) == (7, 1)
    assert m["num_layers"] == 2 * c["num_hidden_layers"]
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["attn_head_dim"],
        m["kv_latent_dim"], m["qk_nope_dim"], m["qk_rope_dim"],
        m["v_head_dim"], m["rope_theta"], m["dense_mlp_dim"], m["mlp_dim"],
        m["shared_expert_dim"], m["moe_top_k"], m["num_experts"],
        m["router_groups"], m["router_groups_kept"], m["routed_scale"],
        m["norm_eps"], m["norm_topk_prob"], m["gdn_conv"],
        m["gdn_decay_bound"], m["qk_norm"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"], pub["head_dim"],
        pub["kv_lora_rank"], pub["qk_nope_head_dim"],
        pub["qk_rope_head_dim"], pub["v_head_dim"], pub["rope_theta"],
        pub["intermediate_size"], pub["moe_intermediate_size"],
        pub["moe_shared_expert_intermediate_size"],
        pub["num_experts_per_tok"], pub["num_experts"], pub["n_group"],
        pub["topk_group"], pub["routed_scaling_factor"],
        pub["rms_norm_eps"], pub["norm_topk_prob"],
        pub["short_conv_kernel_size"], pub["kda_lower_bound"],
        pub["use_qk_norm"],
    )
    # KDA: as many heads as the attention, of head_dim, a decay a channel
    assert pub["num_kv_heads_for_linear_attn"] == 0
    assert (
        m["gdn_value_heads"], m["gdn_key_heads"], m["gdn_key_dim"],
        m["gdn_value_dim"], m["gdn_decay"], m["gdn_gate"],
    ) == (32, 32, pub["head_dim"], pub["head_dim"], "channel",
          "head_sigmoid")
    assert pub["gated_attention_proj_granularity_type"] == "head_wise"
    assert pub["score_function"] == m["router"] == "sigmoid"
    assert m["qk_rope_dim"] == pub["rotary_dim"]
    # no held layer clamps its SwiGLU: the limits are 0 for layers 0-33
    assert not any(pub["expert_swiglu_limit_list"][:8])
    assert not any(pub["share_expert_swiglu_limit_list"][:8])
    # the floors: the dense layer and a whole period, 8 >= 8 experts, an
    # eighth of the vocabulary in whole lane tiles
    assert (c["num_experts"], m["experts_held"]) == (8, 8)
    assert m["vocab_size"] * 8 >= pub["vocab_size"]
    assert m["vocab_size"] % 128 == 0
    assert c["arithmetic"]["parameters"] == family.count(m, 8192)["params"]
    # this router must keep fp32 moments
    assert 2560 * 512 < c["optimizer"]["min_quantized_size"] <= 2 * 2560 * 512


def test_score_lanes_reader():
    mods = harness.load_layer_metrics()
    mod = mods["attn.score_lanes_used_pct"]
    assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
        "kernels", "%", "tokens_per_s"
    )
    config = _config()

    def run(closed, config=config):
        return SimpleNamespace(config=config, window={"pipeline": closed})

    assert mod.read(run(
        {"attn_score_lanes": 256, "attn_score_lanes_used": 192}
    )) == 75.0
    assert mod.read(run(
        {"attn_score_lanes": 192, "attn_score_lanes_used": 192}
    )) == 100.0
    # a program without the counters (the parent's), a step that was not
    # traced, a configuration whose attention states one width: nothing
    assert mod.read(run({"moe_reports": 14})) is None
    assert mod.read(run({"attn_score_lanes": 0,
                         "attn_score_lanes_used": 0})) is None
    assert mod.read(run({"attn_score_lanes": 256})) is None
    assert mod.read(run({})) is None
    assert mod.read(SimpleNamespace(config=config, window={})) is None
    assert mod.read(run(
        {"attn_score_lanes": 256, "attn_score_lanes_used": 256},
        _config("qwen3-next-80b-a3b-d4"),
    )) is None
    names = (
        CELL, "qwen3-next-80b-a3b-d4.steady",
        "nemotron3-nano-30b-a3b-d9.steady", "olmoe-1b-7b-d2.steady",
        "gpt2-124m.steady",
    )
    cells = {n: harness.load_cell(n) for n in names}
    assert [n for n, c in cells.items() if mod.CELLS(c)] == [CELL]
    # the readers that take the cell through their own rules
    for name in ("moe.held_share_pct", "moe.drop_rate_pct",
                 "moe.max_expert_load", "kernel.moe_gmm_roofline",
                 "opt.q8_tiles_share", "kernel.attn_roofline",
                 "gdn.serial_chunk_steps", "gdn.kernel_sites_share",
                 "step.device_ms"):
        assert mods[name].CELLS(cells[CELL]), name
    # a KDA step: 6 sites, none in the kernels
    closed = {"gdn_sites": 6, "gdn_chunk_steps": 1536, "gdn_kernel_sites": 0}
    assert mods["gdn.serial_chunk_steps"].read(run(closed)) == 1536.0
    assert mods["gdn.kernel_sites_share"].read(run(closed)) == 0.0


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-ling3.steady", seed=3000000045, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # G-GE*EGE at width 64: 3 KDA (4 heads of 8 / 8), 1 latent attention
    # (2 heads, latent 32, 16 + 8 | 16), a dense layer of 96, 3 blocks of
    # 8 held experts of 32 beside a shared one of 48
    kda = (
        64 * 96 + 96 * 4 + 64 * 32 + 4 + 32 + 2 * 64 * 4 + 32 * 64 + 8 + 64
    )
    mla = (
        64 * 2 * 24 + 64 * 40 + 32 + 32 * 2 * 32 + 2 * 16 * 64 + 2 * 24 + 64
    )
    dense = 3 * 64 * 96 + 64
    sparse = 64 * 64 + 64 + 3 * 64 * 48 + 64
    assert notes["n_params"] == (
        2 * 256 * 64 + 64 + 3 * kda + mla + dense
        + 3 * (sparse + 8 * 3 * 64 * 32)
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-ling3.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-ling3.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    # three KDA mixers over 64 tokens in chunks of 16, forward and
    # backward: the train step's, not the reference check's forward pass
    assert mods["gdn.serial_chunk_steps"].read(run) == 3 * 4 * 2
    assert mods["gdn.kernel_sites_share"].read(run) == 0.0
    # scores of 24 called with one lane tile of 128
    assert mods["attn.score_lanes_used_pct"].read(run) == 100 * 24 / 128
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # 8 of 64 experts held: an eighth of the assignments, more or less
    assert 4.0 < mods["moe.held_share_pct"].read(run) < 30.0
    assert mods["moe.max_expert_load"].read(run) >= 1.0
