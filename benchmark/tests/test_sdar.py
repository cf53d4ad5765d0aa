"""The benchmark's tests of what the SDAR configuration brought (PR 67),
run by hand beside ``test_benchmark.py`` (which holds ``BENCHMARK.json``
and every data file in agreement, the new ones too):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``flops_sdar.py`` against numbers worked by hand (a row fed twice: 2L
positions through the stack, L through the head, ``L^2 + L B`` pairs a
head; held experts only; everything a DATA token), the configuration file
against the source, the three new readers on hand-made runs, and the CPU
rehearsal of the whole chain at a toy size. Nothing here is a speed.
"""

import json
import os
import sys
from types import SimpleNamespace

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import flops_sdar as family  # noqa: E402
import run as harness  # noqa: E402

NAME = "sdar-30b-a3b-d8"
CELL = f"{NAME}.steady"
L, B = 8192, 4


def _config(name=NAME):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


# one published layer by hand, at the published widths
ATTN = (
    2048 * 32 * 128  # q_proj
    + 2 * 2048 * 4 * 128  # k_proj, v_proj: 4 key/value heads
    + 32 * 128 * 2048  # o_proj
)
NORMS = 2 * 128 + 2 * 2048  # two head norms, the layer's two norms
ROUTER = 2048 * 128
EXPERT = 3 * 2048 * 768
PAIRS = L * L + L * B  # a head's visible pairs of one doubled row


def test_parameters_by_hand():
    c = _config()
    m = c["model"]
    assert (ATTN, ROUTER, EXPERT) == (18874368, 262144, 4718592)
    layer = ATTN + NORMS + ROUTER + 16 * EXPERT
    assert layer == 94638336
    held = 8 * layer + 2 * 18992 * 2048 + 2048
    assert held == 834899968
    got = family.count(m, L)
    assert got["params"] == held == c["arithmetic"]["parameters"]
    # the published model: 48 layers, all 128 experts, 151,936 rows
    published = dict(
        m, num_layers=96, layer_pattern="*E" * 48, experts_held=128,
        vocab_size=151936,
    )
    whole = 48 * (ATTN + NORMS + ROUTER + 128 * EXPERT) \
        + 2 * 151936 * 2048 + 2048
    assert whole == 30532122624
    assert family.count(published, L)["params"] == whole
    assert ATTN + NORMS + ROUTER + 128 * EXPERT == 623120640
    # a token of the published model: 8 of its 128 experts a layer
    active = family.count(published, L)["active_params"]
    assert active == 48 * (ATTN + NORMS + ROUTER + 8 * EXPERT) \
        + 2 * 151936 * 2048 + 2048
    assert 3.3e9 < active < 3.4e9
    # held here, a position meets 8 x 16 / 128 = 1 of the held experts
    assert got["active_params"] == held - 8 * 15 * EXPERT


def test_operations_a_data_token_by_hand():
    m = _config()["model"]
    assert PAIRS == 67141632 == family.visible_pairs(L, B)
    # one layer's forward over the 2L positions of a row, in TFLOP
    projections = 2.0 * ATTN * 2 * L
    router = 2.0 * ROUTER * 2 * L
    experts = 2.0 * EXPERT * (2 * L * 8 * 16 / 128)
    scores_values = 2 * 2.0 * PAIRS * 128 * 32
    assert [round(x / 1e12, 3) for x in (
        projections, router, experts, scores_values
    )] == [0.618, 0.009, 0.155, 1.1]
    head = 2.0 * 2048 * 18992 * L  # the noised half alone
    row = 3 * (8 * (projections + router + experts + scores_values) + head)
    got = family.count(m, L)
    assert abs(got["train_flops_per_token"] * L - row) < 1e-6 * row
    assert round(row / 1e12, 1) == 47.1
    assert round(got["train_flops_per_token"] / 1e9, 2) == 5.75
    shares = {
        k: 100 * v / got["train_flops_per_token"]
        for k, v in got["by_kind"].items()
    }
    assert [round(shares[k], 1) for k in (
        "scores_values", "projections", "head"
    )] == [56.1, 31.5, 4.1]
    assert round(shares["routers"] + shares["experts"], 1) == 8.3
    # a malformed model group is refused
    for bad in (
        dict(layer_pattern="*E*"), dict(layer_pattern="WE" * 8),
        dict(objective=""),
    ):
        try:
            family.count(dict(m, **bad), L)
        except ValueError:
            continue
        raise AssertionError(bad)
    try:
        family.visible_pairs(30, 4)
    except ValueError:
        pass
    else:
        raise AssertionError("a row of no whole blocks")


def test_step_work_is_the_two_kinds_of_kernel():
    m = _config()["model"]
    work = family.step_work(m, 1, L)
    # the walk: six matmuls over the visible pairs, 32 heads of 128, eight
    # layers; q, k, v, o and cotangents of the 2L positions once
    walk = work["attention_block_diffusion"]
    assert work["attention"] == walk
    assert walk["flops"] == 8 * 6 * 2.0 * PAIRS * 128 * 32
    assert walk["bytes"] == 8 * 11 * (32 * 2 * L * 128 * 2)
    # a quarter of the square's pairs and a little: what
    # attn.bd_blocks_walked_pct is held against
    assert round(100.0 * PAIRS / (2 * L) ** 2, 2) == 25.01
    # bound by operations on a v5e
    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(walk, peak)["bound"] == "flops"
    # the held experts' rows: 2L positions x 8 x 16 / 128 a layer
    rows = 2 * L * 8 * 16 / 128
    assert family.held_rows(m, L) == rows == 16384
    gmm = work["grouped_matmul"]
    assert gmm["flops"] == 8 * 3 * 3 * 2.0 * rows * 2048 * 768
    # a batch of two rows: twice the work of one
    two = family.step_work(m, 2, L)
    assert two["attention"]["flops"] == 2 * walk["flops"]
    assert two["grouped_matmul"]["flops"] == 2 * gmm["flops"]


def test_the_file_holds_the_source_and_only_the_cut_differs():
    c = _config()
    m, pub = c["model"], c["published"]
    cut = {"num_hidden_layers", "vocab_size"}
    for key, value in pub.items():
        assert (c[key] == value) == (key not in cut), key
    assert set(c["reduced"]) == cut | {
        "num_layers", "layer_pattern", "experts_held"
    }
    assert set(c["reduced_from"]) == set(c["reduced"])
    for key, (published, here) in c["reduced_from"].items():
        if key in pub:
            assert (pub[key], c[key]) == (published, here), key
    assert c["vocab_size"] == m["vocab_size"]
    assert c["source"].endswith("JetLM/SDAR-30B-A3B-Chat/blob/main/config.json")
    # every published layer is attention then experts
    assert pub["decoder_sparse_step"] == 1 and pub["mlp_only_layers"] == []
    assert m["layer_pattern"] == "*E" * c["num_hidden_layers"]
    assert m["num_layers"] == 2 * c["num_hidden_layers"]
    # every width as published
    assert (
        m["model_dim"], m["num_heads"], m["num_kv_heads"],
        m["attn_head_dim"], m["rope_theta"], m["mlp_dim"], m["moe_top_k"],
        m["num_experts"], m["norm_eps"], m["norm_topk_prob"],
        m["tie_embeddings"],
    ) == (
        pub["hidden_size"], pub["num_attention_heads"],
        pub["num_key_value_heads"], pub["head_dim"], pub["rope_theta"],
        pub["moe_intermediate_size"], pub["num_experts_per_tok"],
        pub["num_experts"], pub["rms_norm_eps"], pub["norm_topk_prob"],
        pub["tie_word_embeddings"],
    )
    assert pub["rope_scaling"] is None and m["rope"] is True
    assert pub["use_sliding_window"] is False and "attn_window" not in m
    assert (m["qk_norm"], m["qk_norm_span"]) == (True, "head")
    assert "shared_expert_dim" not in m and m["router"] == "softmax"
    # the objective and what the row does not give
    assert (m["objective"], m["diffusion_block"]) == ("block_diffusion", B)
    assert m["diffusion_mask_id"] == m["vocab_size"] - 1
    assert m["max_seq_len"] % m["diffusion_block"] == 0
    # the floors: a whole period and four layers, 16 >= 8 experts, an
    # eighth of the vocabulary
    assert c["num_hidden_layers"] >= 4
    assert m["experts_held"] == 16 and c["num_experts"] == 128
    assert m["vocab_size"] * 8 == pub["vocab_size"]
    for item in (
        "block_length", "noise_schedule", "aligned_prediction",
        "doubled_row", "mask_id", "noise", "router", "dtype", "optimizer",
        "init", "rows", "remat",
    ):
        assert item in c["assumed"], item
    assert "8 chips" in c["deployment"]
    # the router, W_k, W_v and the norms keep fp32 moments
    assert 2048 * 512 < c["optimizer"]["min_quantized_size"]
    cell = harness.load_cell(CELL)
    assert (cell["batch"], cell["seq"], cell["chips"], cell["moe"]) == (
        1, L, 1, True
    )
    assert cell["seq"] == m["max_seq_len"]
    assert cell["trace"]["steps"] == 20 and not cell["kill"]
    assert len(cell["why"]) <= 200 and "data tokens" in cell["why"]


def test_the_three_readers():
    mods = harness.load_layer_metrics()
    walked = mods["attn.bd_blocks_walked_pct"]
    roof = mods["kernel.attn_bd_roofline"]
    masked = mods["diffusion.masked_share_pct"]
    for mod in (walked, roof):
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            "kernels", "%", "tokens_per_s"
        )
    assert (masked.LAYER, masked.UNIT, masked.MOVES) == (
        "step program", "%", "tokens_per_s"
    )
    assert roof.CEILING == 100.0 and not hasattr(walked, "CEILING")
    config = _config()

    def run(closed, config=config, opened=None):
        return SimpleNamespace(config=config, window={
            "pipeline": closed, "pipeline_open": opened or {},
        })

    # eight layers' forward (traced twice) and backward, blocks of 1024
    assert walked.read(run({
        "attn_bd_blocks_walked": 24 * 80, "attn_bd_blocks_square": 24 * 256,
    })) == 31.25
    # the rule as a mask over the rectangular grid
    assert walked.read(run({
        "attn_bd_blocks_walked": 3072, "attn_bd_blocks_square": 3072,
    })) == 100.0
    # a program without the counters (the parent's), the jnp path, a
    # configuration trained by next-token prediction: nothing
    assert walked.read(run({"moe_reports": 14})) is None
    assert walked.read(run({"attn_bd_blocks_square": 0,
                            "attn_bd_blocks_walked": 0})) is None
    assert walked.read(run({"attn_bd_blocks_square": 256})) is None
    assert walked.read(SimpleNamespace(config=config, window={})) is None
    other = _config("trinity-mini-d5")
    assert walked.read(run({
        "attn_bd_blocks_walked": 80, "attn_bd_blocks_square": 256,
    }, other)) is None
    # the masked share: the rise of the sum over the rise of the reports
    assert masked.read(run(
        {"diffusion_reports": 9, "diffusion_masked_sum": 4.5},
        opened={"diffusion_reports": 5, "diffusion_masked_sum": 2.5},
    )) == 50.0
    assert masked.read(run({"diffusion_reports": 5}, opened={
        "diffusion_reports": 5,
    })) is None
    assert masked.read(run({"moe_reports": 14})) is None
    assert masked.read(run(
        {"diffusion_reports": 2, "diffusion_masked_sum": 1.0}, other
    )) is None
    names = (
        CELL, "trinity-mini-d5.steady", "olmoe-1b-7b-d2.steady",
        "ouro-2.6b-d6.steady", "gpt2-124m.steady",
    )
    for mod in (walked, roof, masked):
        assert [mod.CELLS(harness.load_cell(n)) for n in names] == [
            True, False, False, False, False
        ]

    # the roofline reader on a hand-made trace: the walk's kernels alone
    def op(name, seconds):
        return {"name": name, "about": "custom-call tpu_custom_call",
                "total_s": seconds, "count": 1}

    peak = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    work = family.step_work(config["model"], 1, L)
    least = work["attention_block_diffusion"]["flops"] / peak["bf16_flops"]
    device = {"steps": 2, "ops": [
        op("%flash_attn_bd_fwd.3", 2 * least),
        op("%flash_attn_bd_bwd.4", 2 * least),
        op("%flash_attn_fwd.5", 1.0),  # another model's: not the walk's
        op("%fusion.7", 5.0),
    ]}
    traced = SimpleNamespace(
        config=config, cell=harness.load_cell(CELL), hook=family, peak=peak,
        trace={"devices": [device]},
    )
    assert abs(roof.read(traced) - 50.0) < 1e-9
    # kernel.attn_roofline sums them with every other attention kernel
    whole = mods["kernel.attn_roofline"].read(traced)
    assert whole is not None and whole < 50.0
    # no such kernel in the trace (the rule as a mask), no trace, a family
    # module that counts no such work (the parent's benchmark files)
    device["ops"] = device["ops"][2:]
    assert roof.read(traced) is None
    traced.trace = None
    assert roof.read(traced) is None
    traced.trace = {"devices": [device]}
    traced.hook = SimpleNamespace(
        step_work=lambda *a: {"attention": work["attention"]}
    )
    assert roof.read(traced) is None


def test_cpu_rehearsal_of_the_cell(capsys):
    res = harness.run_cell(
        "toy-sdar.steady", seed=3000000067, seconds=2.0, trace=False,
        device_spec="cpu:1", expect_platform="cpu",
        data_dir=os.path.join(HERE, "rehearsal"),
    )
    assert res["failed"] == 0
    assert res["device"]["platform"] == "cpu"  # never a device metric
    notes = next(n for n in harness.json_lines(capsys.readouterr().out)
                 if isinstance(n, dict) and "n_params" in n)
    # the program's loss is the reference's, and nothing is wrong with the
    # run but, on some draws, that its last step's loss is no lower than
    # its first: at 512 positions a step the 1 / t weights scatter a
    # step's loss by a tenth either way (one position of a block at t =
    # 0.01 weighs 100 of them), as much as the toy's twenty steps learn.
    # The cell's 8,192 positions a step scatter it by 2.7 % (PERF.md)
    assert notes["reference_check"]["abs_diff"] <= 1e-4
    assert res["correct"] is True or [
        p.split(":")[0] for p in notes["problems"]
    ] == ["loss did not fall"]
    # *E*E at width 64: 2 attention layers (4 heads on 2 of 16, a norm a
    # head), 2 layers of 8 held experts of 32 of 16 routed over
    attn = 2 * 64 * 4 * 16 + 2 * 64 * 2 * 16 + 2 * 16 + 64
    sparse = 64 * 16 + 64 + 8 * 3 * 64 * 32
    assert notes["n_params"] == 2 * 256 * 64 + 64 + 2 * (attn + sparse)
    assert notes["mfu_pct"] is None  # no peak: the CPU
    run_dir = os.path.join(os.path.dirname(BENCH), ".benchmark_run",
                           "toy-sdar.steady")
    with open(os.path.join(run_dir, "window_r0.json")) as f:
        window = json.load(f)
    with open(os.path.join(HERE, "rehearsal", "configs",
                           "toy-sdar.json")) as f:
        config = json.load(f)
    mods = harness.load_layer_metrics()
    run = SimpleNamespace(window=window, config=config)
    pipeline = window["pipeline"]
    # two sites under the rule, 2 x 8 x 64 positions of 8 x 64 data tokens
    assert pipeline["attn_bd_sites"] == 2
    assert (pipeline["diffusion_positions"],
            pipeline["diffusion_data_tokens"]) == (1024, 512)
    # on the CPU the attention is the jnp path: no block is counted
    assert mods["attn.bd_blocks_walked_pct"].read(run) is None
    # 128 positions a step in blocks of 4: near a half, give or take
    assert 25.0 < mods["diffusion.masked_share_pct"].read(run) < 75.0
    assert mods["moe.drop_rate_pct"].read(run) == 0.0
    # 8 of 16 experts held: half of the assignments, more or less
    assert 25.0 < mods["moe.held_share_pct"].read(run) < 75.0
