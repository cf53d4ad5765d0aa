"""The benchmark's tests of what the Mistral-Small-4-119B configuration
brought (PR 59), run by hand beside ``test_benchmark.py`` (which holds
``BENCHMARK.json`` and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_mistral4.py`` against numbers worked by hand (118,972,826,624
parameters on the published depth, experts and rows, 1,154,524,160 held,
3.454 GFLOP a token at 16,384), the configuration file against the source,
the two new readers on hand-made runs, and one CPU rehearsal of the cell
through the whole chain. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_mistral4 as family  # noqa: E402
import flops_moe  # noqa: E402
import run as harness  # noqa: E402

NAME = "mistral-small-4-119b-d4"
CELL = f"{NAME}.steady"
T = 16384
# one published layer outside its routed experts, by hand
ATTN = (
    4096 * 1024 + 1024 + 1024 * 32 * 128      # w_qa, its norm, w_qb
    + 4096 * (256 + 64) + 256                 # w_kva, the latent's norm
    + 256 * 32 * (64 + 128)                   # w_kvb
    + 32 * 128 * 4096                         # wo
)
EXPERT = 3 * 4096 * 2048
LAYER = ATTN + 2 * 4096 + 4096 * 128 + EXPERT  # + norms, router, shared


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_the_family_counts_the_published_model_and_the_cut():
    model = _config()["model"]
    assert (ATTN, EXPERT, LAYER) == (28_050_688, 25_165_824, 53_748_992)
    held = family.count(model, T)
    assert held["params"] == (
        4 * (LAYER + 8 * EXPERT) + 2 * 16384 * 4096 + 4096
    ) == 1_154_524_160
    deep = dict(model, num_layers=72, layer_pattern="*E" * 36,
                experts_held=128, vocab_size=131072)
    whole = family.count(deep, T)
    assert whole["params"] == (
        36 * (LAYER + 128 * EXPERT) + 2 * 131072 * 4096 + 4096
    ) == 118_972_826_624
    # a token passes 4 of the 128: the catalog's "A6.5B" with both tables
    assert whole["active_params"] == (
        36 * (LAYER + 4 * EXPERT) + 2 * 131072 * 4096 + 4096
    )
    assert abs(whole["active_params"] / 1e9 - 6.63) < 0.01
    assert _config()["arithmetic"]["parameters"] == held["params"]
    # here a token meets 4 * 8 / 128 of an expert a layer at balance
    assert held["active_params"] == (
        4 * LAYER + EXPERT + 2 * 16384 * 4096 + 4096
    )


def test_a_tokens_operations_by_hand():
    model = _config()["model"]
    got = family.count(model, T)
    scores = 3 * 2 * 32 * (128 + 128) * T // 2  # Q K^T and P V, causal half
    projections = 6 * (ATTN - 1024 - 256)
    want = {
        "scores_values": 4 * scores, "projections": 4 * projections,
        "shared": 4 * 6 * EXPERT, "router": 4 * 6 * 4096 * 128,
        "held_experts": 4 * 6 * EXPERT * 4 * 8 // 128,
        "head": 6 * 4096 * 16384,
    }
    assert got["by_kind"] == want
    total = got["train_flops_per_token"]
    assert total == sum(want.values())
    assert abs(total / 1e9 - 3.454) < 5e-4
    assert abs(total * T / 1e12 - 56.6) < 0.05
    share = {k: round(100 * v / total, 1) for k, v in want.items()}
    assert share == {
        "scores_values": 46.6, "projections": 19.5, "shared": 17.5,
        "router": 0.4, "held_experts": 4.4, "head": 11.7,
    }
    short = family.count(model, 8192)["train_flops_per_token"]
    assert abs(short / 1e9 - 2.649) < 5e-4


def test_the_kernels_work_is_four_layers_of_each():
    model = _config()["model"]
    work = family.step_work(model, 1, T)
    one = flops.attention_kernel_work(1, 32, T, 128)
    assert work["attention"] == {k: 4 * v for k, v in one.items()}
    # 16384 tokens x 4 a token x 8 / 128 = 4096 rows a layer, 512 a held
    # expert, through the three projections of eight 4096 x 2048 experts
    assert family.held_rows(model, T) == 4096
    rows = flops_moe.grouped_matmul_work(
        {"model_dim": 4096, "mlp_dim": 2048, "swiglu": True, "moe_top_k": 1,
         "num_experts": 8}, 4096,
    )
    assert work["grouped_matmul"] == {k: 4 * v for k, v in rows.items()}
    assert rows["flops"] == 3 * 3 * 2 * 4096 * 4096 * 2048
    # score and value widths that differ are counted each at its own
    wide = family.attention_work(dict(model, qk_nope_dim=128), 1, T)
    assert wide["flops"] == one["flops"] * (192 + 128) / 256


@pytest.mark.parametrize("nonsense", [
    dict(q_latent_dim=0), dict(attn_kind=""), dict(num_layers=10),
    dict(layer_pattern="*E*E*E*-"),
])
def test_the_family_refuses_another_shape(nonsense):
    with pytest.raises(ValueError):
        family.count(dict(_config()["model"], **nonsense), T)


def test_the_configuration_is_the_source_cut_in_depth_share_and_rows():
    config = _config()
    published, model = config["published"], config["model"]
    changed = {k for k in published if config.get(k) != published[k]}
    assert changed == {"num_hidden_layers", "vocab_size"}
    assert set(config["reduced"]) == {
        "num_hidden_layers", "num_layers", "layer_pattern", "experts_held",
        "vocab_size",
    }
    assert config["reduced_from"]["experts_held"] == [128, 8]
    rope = published["rope_parameters"]
    assert (
        model["model_dim"], model["num_heads"], model["q_latent_dim"],
        model["kv_latent_dim"], model["qk_nope_dim"], model["qk_rope_dim"],
        model["v_head_dim"], model["mlp_dim"], model["num_experts"],
        model["moe_top_k"], model["routed_scale"], model["norm_eps"],
    ) == (
        published["hidden_size"], published["num_attention_heads"],
        published["q_lora_rank"], published["kv_lora_rank"],
        published["qk_nope_head_dim"], published["qk_rope_head_dim"],
        published["v_head_dim"], published["moe_intermediate_size"],
        published["n_routed_experts"], published["num_experts_per_tok"],
        published["routed_scaling_factor"], published["rms_norm_eps"],
    )
    assert model["shared_expert_dim"] == (
        published["n_shared_experts"] * published["moe_intermediate_size"]
    )
    assert (
        model["rope_scaling"], model["rope_theta"], model["rope_factor"],
        model["rope_original_len"], model["rope_beta_fast"],
        model["rope_beta_slow"], model["rope_mscale_all_dim"],
        model["attn_pos_scale_beta"],
    ) == (
        rope["rope_type"], rope["rope_theta"], rope["factor"],
        rope["original_max_position_embeddings"], rope["beta_fast"],
        rope["beta_slow"], rope["mscale_all_dim"],
        rope["llama_4_scaling_beta"],
    )
    assert (model["rope_pairs"] == "interleaved") is published[
        "rope_interleave"
    ]
    assert model["tie_embeddings"] is published["tie_word_embeddings"]
    assert published["first_k_dense_replace"] == 0
    assert model["layer_pattern"] == "*E" * config["num_hidden_layers"]
    assert model["vocab_size"] * 8 == published["vocab_size"]
    check = config["reference_check"]
    program, control = check["program_abs_diff"], check["float8_abs_diff"]
    assert len(program) >= 8 and len(control) >= 3
    assert max(program) < check["tolerance"] < sorted(control)[1]


def test_every_line_of_benchmark_json_fits_its_200_characters():
    # the driver refuses the whole file for one `why` of 201 (this PR's
    # first check); test_benchmark.py counts the cells' lines only
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    entries = sum((bench[k] for k in (
        "configs", "workloads", "end_to_end", "per_layer")), [])
    lines = [e[k] for e in entries for k in ("why", "layer", "source")
             if k in e] + bench["command"]
    assert len(lines) > 100
    for line in lines:
        assert 1 <= len(line) <= 200 and line.isprintable(), line


def test_the_new_readers_on_hand_made_runs():
    mods = harness.load_layer_metrics()
    sites, rows = (
        mods["attn.q_latent_sites_per_step"], mods["attn.pos_scaled_rows_pct"]
    )
    config, cell = _config(), harness.load_cell(CELL)
    assert sites.CELLS(cell) and rows.CELLS(cell)
    other = harness.load_cell("ling-3.0-flash-d7.steady")
    assert not sites.CELLS(other) and not rows.CELLS(other)
    # the accepted readers whose rule takes the new cell, and some whose
    # rule does not
    takes = {
        "moe.drop_rate_pct": True, "moe.max_expert_load": True,
        "kernel.moe_gmm_roofline": True, "moe.gmm_runs_per_step": True,
        "moe.held_share_pct": True, "opt.q8_tiles_share": True,
        "attn.fwd_kernel_runs_per_step": True,
        "attn.edge_tiles_multiplied_pct": True,
        "attn.score_lanes_used_pct": False,
        "attn.window_blocks_walked_pct": False,
        "gdn.serial_chunk_steps": False, "ut.layer_passes_per_step": False,
    }
    assert {n: mods[n].CELLS(cell) for n in takes} == takes
    run = SimpleNamespace(config=config, cell=cell, window={"pipeline": {
        "attn_q_latent_sites": 4, "attn_pos_rows": 65536,
        "attn_pos_scaled_rows": 32768,
    }})
    assert sites.read(run) == 4.0 and rows.read(run) == 50.0
    # rows of 8192: every query under the table's length
    run.window["pipeline"].update(attn_pos_rows=32768, attn_pos_scaled_rows=0)
    assert rows.read(run) == 0.0
    # a program without the counters (the parent's); a configuration
    # whose query is projected whole and has no such scale
    run.window = {"pipeline": {"moe_reports": 3}}
    assert sites.read(run) is None and rows.read(run) is None
    run.window = {"pipeline": {"attn_q_latent_sites": 4, "attn_pos_rows": 8,
                               "attn_pos_scaled_rows": 4}}
    run.config = _config("ling-3.0-flash-d7")
    assert sites.read(run) is None and rows.read(run) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-mistral4.steady", seed=3000000059, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["correct"] is True and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # two published layers at width 64: 4 heads of 8 + 8 / 16, latents of
    # 32 and 16, 4 of 8 experts of 32 held, a shared expert of 32, two
    # tables of 256 rows, the final norm
    attn = (64 * 32 + 32 + 32 * 4 * 16 + 64 * 24 + 16 + 16 * 4 * 24
            + 4 * 16 * 64)
    block = 2 * 64 + 64 * 8 + 3 * 64 * 32
    assert notes["n_params"] == (
        2 * (attn + block + 4 * 3 * 64 * 32) + 2 * 256 * 64 + 64
    )
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-mistral4.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-mistral4.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    pipeline = window["pipeline"]
    assert (pipeline["attn_q_latent_sites"], pipeline["rope_scaled_sites"],
            pipeline["attn_pos_rows"], pipeline["attn_pos_scaled_rows"]) == (
        2, 2, 2 * 2 * 64, 0
    )
    assert mods["attn.q_latent_sites_per_step"].read(run) == 2.0
    assert mods["attn.pos_scaled_rows_pct"].read(run) == 0.0
    # the toy's 16-wide heads are called at a whole lane tile; the
    # published 128 are one, and the cell's call pads nothing
    assert (pipeline["attn_score_lanes"],
            pipeline["attn_score_lanes_used"]) == (2 * 128, 2 * 16)
    # on the CPU the attention is the jnp path: no kernel site is counted
    assert mods["attn.edge_tiles_multiplied_pct"].read(run) is None
