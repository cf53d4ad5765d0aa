"""The hook between a configuration and the benchmark's arithmetic (PR 35):
``configs/<name>.json``'s ``flops`` names the family module whose ``count``
and ``step_work`` feed ``run.py``'s notes and the two roofline readers. Run
by hand, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Three things are held here. The two family modules the cells use reach, by
the new road, the very floats the readers before PR 35 reached by
``num_layers`` x one layer's work (that arithmetic is written out below, not
imported). A family made by hand whose layers are not all alike reads the
share worked by hand, where that arithmetic reads a multiple of it. And what
cannot be right is refused: a share above its reader's ceiling, a family
module that is missing, lacks a function or cannot count the model.
"""

import gzip
import json
import os
import shutil
import sys
from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)
sys.path.insert(0, HERE)

import flops  # noqa: E402
import flops_moe  # noqa: E402
import peaks  # noqa: E402
import record_stretch  # noqa: E402
import run as harness  # noqa: E402
import xplane  # noqa: E402

PEAK = peaks.peaks("TPU v5 lite")
CALL = "custom_call_target=tpu_custom_call"
READERS = ("kernel.attn_roofline", "kernel.moe_gmm_roofline")


def _config(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def _cell(name):
    with open(os.path.join(BENCH, "cells", f"{name}.json")) as f:
        return json.load(f)


def _run(config, cell, trace, hook=None):
    return SimpleNamespace(
        config=config, cell=cell, trace=trace, peak=PEAK,
        hook=hook or harness.load_hook("x", config),
    )


def _readers():
    mods = harness.load_layer_metrics()
    return {name: mods[name] for name in READERS}


# -- count: the figures the tests and PERF.md hold by hand --------------------
def test_count_of_the_dense_family_by_hand():
    m = _config("gpt2-124m")["model"]
    layer = 4 * 768**2 + 2 * 768 * 3072 + 3072 + 768 + 4 * 768
    params = 50257 * 768 + 1024 * 768 + 12 * layer + 2 * 768
    assert params == 124_402_944
    got = flops.count(m, 1024)
    assert got == {
        "params": params, "active_params": params,
        "train_flops_per_token": 6.0 * params + 6.0 * 12 * 1024 * 768,
    }
    assert abs(got["train_flops_per_token"] / 1e9 - 0.803) < 5e-4
    x = flops.count(_config("gpt2-xl-d12")["model"], 1024)
    layer = 4 * 1600**2 + 2 * 1600 * 6400 + 6400 + 1600 + 4 * 1600
    assert x["params"] == x["active_params"] == (
        50257 * 1600 + 1024 * 1600 + 12 * layer + 2 * 1600)
    assert abs(x["params"] / 1e6 - 450.9) < 0.05
    assert abs(x["train_flops_per_token"] / 1e9 - 2.823) < 5e-4


def test_count_of_the_sparse_family_by_hand():
    c = _config("olmoe-1b-7b-d2")
    assert c["flops"] == "flops_moe"
    got = harness.load_hook("olmoe-1b-7b-d2", c).count(c["model"], 4096)
    assert got["params"] == 1_045_186_560 == c["arithmetic"]["parameters"]
    assert got["active_params"] == 340_543_488 == c["arithmetic"][
        "active_parameters_a_token"]
    by_hand = 6 * (
        2 * (4 * 2048**2 + 2048 * 64 + 8 * 3 * 2048 * 1024) + 2048 * 50304
    ) + 6 * 2 * 4096 * 2048
    assert got["train_flops_per_token"] == by_hand
    assert abs(by_hand / 1e9 - 1.526) < 5e-4
    # the note run.py prints for the cell: 28.1 % at the ledger's 36,256
    # tokens/s (PR 33), where the dense count of the same group gave less
    mfu = flops.mfu_pct(36256.0, by_hand, PEAK["bf16_flops"])
    assert 28.0 < mfu < 28.2
    assert flops.count(c["model"], 4096)["params"] < 0.3 * got["params"]


@pytest.mark.parametrize("cell", [
    "gpt2-124m.steady", "gpt2-124m.save-kill-resume", "gpt2-xl-d12.steady",
    "olmoe-1b-7b-d2.steady",
])
def test_step_work_is_every_layer_of_the_one_block(cell):
    """The four cells' layers are all alike, so a step's work is exactly
    ``num_layers`` times one layer's: the old arithmetic by another road."""
    cell = _cell(cell)
    config = _config(cell["config"])
    m = config["model"]
    batch, seq = cell["batch"], cell["seq"]
    got = harness.load_hook("x", config).step_work(m, batch, seq)
    layer = flops.attention_kernel_work(
        batch, m["num_heads"], seq, m["model_dim"] // m["num_heads"])
    assert got["attention"] == {
        k: m["num_layers"] * v for k, v in layer.items()}
    if m.get("num_experts"):
        layer = flops_moe.grouped_matmul_work(m, batch * seq)
        assert got["grouped_matmul"] == {
            k: m["num_layers"] * v for k, v in layer.items()}
    else:
        assert got["grouped_matmul"] is None
    hook = harness.load_hook("x", config)
    assert harness.ask_hook(hook, "x", m, batch, seq) == hook.count(m, seq)


# -- the same floats as the readers before PR 35, on the same traces ----------
def _parents_share(run, kind):
    """What the reader of the parent commit returned: the model's shape
    reckoned in the reader, ``num_layers`` layers of ``num_heads`` heads of
    ``model_dim // num_heads``, every layer sparse with all its experts."""
    m = run.config["model"]
    device = run.trace["devices"][0]
    if kind == "kernel.attn_roofline":
        named = [r for r in device["ops"] if "flash_attn" in r["name"].lower()]
        seconds = xplane.kernel_seconds(
            {"ops": named}, ("tpu_custom_call",))["seconds"]
        work = flops.attention_kernel_work(
            run.cell["batch"], m["num_heads"], run.cell["seq"],
            m["model_dim"] // m["num_heads"],
        )
    else:
        seconds = sum(
            r["total_s"] for r in device["ops"]
            if r["name"].startswith("%ragged-dot"))
        work = flops_moe.grouped_matmul_work(
            m, run.cell["batch"] * run.cell["seq"])
    layers = m["num_layers"] * device["steps"]
    work = {k: v * layers for k, v in work.items()}
    return 100.0 * flops.roofline_seconds(work, run.peak)["seconds"] / seconds


def _recorded_trace():
    """The recorded stretch of a real OLMoE run. The recording dropped the
    events' stats: the kernels get back the one the readers match on."""
    with gzip.open(
        os.path.join(HERE, "data", "olmoe-1b-7b-d2.steady.stretch.json.gz"),
        "rt",
    ) as f:
        plane = record_stretch.recorded_plane(json.load(f))
    ops = plane["lines"][1]
    assert ops["name"] == xplane.OPS_LINE
    ops["events"] = [
        (name, start, dur, {"custom_call_target": "tpu_custom_call"}
         if "flash_attn" in name or name.startswith("%ragged-dot") else stats)
        for name, start, dur, stats in ops["events"]
    ]
    return xplane.reduce_planes([plane])


def _built_trace(per_step, steps=21):
    """A device plane of ``steps`` executions, a millisecond apart, each
    holding ``per_step``'s ``(name, start_ns, dur_ns, stats)`` operations."""
    import test_trace_window as planes

    runs = planes._steps(steps, period=1e6, dur=0.9e6)
    plane = planes._device(runs)
    plane["lines"][2]["events"] = [
        (name, start + at, dur, stats)
        for _p, start, _d in runs for name, at, dur, stats in per_step
    ]
    return xplane.reduce_planes([plane])


def _built_sparse():
    call = {"custom_call_target": "tpu_custom_call"}
    return _built_trace([
        ("%flash_attn_fused_fwd.1", 1e5, 1e5, call),
        ("%flash_attn_bwd_dkv.2", 2e5, 2e5, call),
        ("%ragged-dot-none.3", 4e5, 3e5, call),
        ("%ragged-dot-metadata.1", 7e5, 1e3, call),
        ("%fusion.9", 7.1e5, 1e5,
         {"hlo": "f32[8] fusion(%flash_attn_fused_fwd.1, %ragged-dot-none.3)"}),
    ])


def _built_dense():
    call = {"custom_call_target": "tpu_custom_call"}
    return _built_trace([
        (f"%flash_attn_fused_{d}.{i}", 1e5 * (1 + 2 * i), 9e4, call)
        for i, d in enumerate(("fwd", "bwd"))
    ], steps=9)


def _rows_by_hand():
    """A reduced trace written down as rows (as test_moe's): 20 steps."""
    rows = [
        {"name": "%ragged-dot-none.3", "count": 40, "total_s": 1.0853,
         "self_s": 1.0853, "about": CALL},
        {"name": "%flash_attn_fwd.2", "count": 40, "total_s": 0.3151,
         "self_s": 0.3151, "about": CALL},
        {"name": "%fusion.1", "count": 40, "total_s": 1.0, "self_s": 1.0,
         "about": "hlo=bf16[8,8] fusion(bf16[8,8] %ragged-dot-none.3)"},
    ]
    return {"steps": 20, "devices": [{"ops": rows, "steps": 20}]}


@pytest.mark.parametrize("cell,trace", [
    ("olmoe-1b-7b-d2.steady", _recorded_trace),
    ("olmoe-1b-7b-d2.steady", _built_sparse),
    ("olmoe-1b-7b-d2.steady", _rows_by_hand),
    ("gpt2-124m.steady", _built_dense),
    ("gpt2-124m.save-kill-resume", _built_dense),
    ("gpt2-xl-d12.steady", _built_dense),
], ids=lambda v: v if isinstance(v, str) else v.__name__.strip("_"))
def test_the_readers_return_the_parents_floats(cell, trace, capsys):
    """The readers themselves, not ``read_layer_metrics``: a built plane's
    kernels last nanoseconds and read thousands of per cent."""
    recorded = trace is _recorded_trace
    cell, trace = _cell(cell), trace()
    run = _run(_config(cell["config"]), cell, trace)
    sparse = bool(run.config["model"].get("num_experts"))
    got = {name: mod.read(run) for name, mod in _readers().items()}
    assert (got["kernel.moe_gmm_roofline"] is not None) == sparse
    for name, value in got.items():
        if value is not None:
            assert value == _parents_share(run, name)  # bit for bit
    lines = harness.json_lines(capsys.readouterr().out)
    assert [ln["steps_traced"] for ln in lines] == [
        trace["steps"]] * (1 + sparse)
    if recorded:
        # the run the stretch was recorded from (PERF.md, Findings PR 33)
        assert trace["steps"] == 19
        assert 25.2 < got["kernel.attn_roofline"] < 25.3
        assert 46.0 < got["kernel.moe_gmm_roofline"] < 46.7
        through = harness.read_layer_metrics(_readers(), run)
        assert {k: v[0] for k, v in through.items()} == got


# -- a family made by hand, which the old readers would have got wrong --------
FAMILY = '''"""A family made for the test: ``layers`` names each layer's kind, one
letter a layer: ``*`` attention over ``num_heads`` heads of ``head_dim``
(not model_dim / num_heads), ``E`` experts of two projections of which
this chip holds ``experts_held`` of ``num_experts``, ``M`` neither."""

from flops import attention_kernel_work


def count(model, seq):
    d, f = model["model_dim"], model["mlp_dim"]
    attn = 4 * d * model["num_heads"] * model["head_dim"]
    sparse = model["layers"].count("E")
    shared = model["layers"].count("*") * attn + sparse * d * model[
        "num_experts"] + 2 * model["vocab_size"] * d
    return {
        "params": shared + sparse * model["experts_held"] * 2 * d * f,
        "active_params": shared + sparse * model["moe_top_k"] * 2 * d * f,
        "train_flops_per_token": 6.0 * (
            shared - model["vocab_size"] * d
            + sparse * model["moe_top_k"] * 2 * d * f),
    }


def step_work(model, batch, seq):
    one = attention_kernel_work(
        batch, model["num_heads"], seq, model["head_dim"])
    d, f = model["model_dim"], model["mlp_dim"]
    held = model["experts_held"]
    # even routing: a chip sees its share of the batch's assignments
    rows = batch * seq * model["moe_top_k"] * held // model["num_experts"]
    sparse = model["layers"].count("E")
    grouped = {
        "flops": sparse * 2 * 3 * 2.0 * rows * d * f,
        "bytes": float(sparse * 2 * 3 * (rows * d + rows * f + held * d * f)
                       * 2),
    }
    return {
        "attention": ATTENTION,
        "grouped_matmul": GROUPED,
    }
'''
MODEL = {
    "vocab_size": 32000, "model_dim": 1024, "num_layers": 3, "layers": "E*E",
    "num_heads": 4, "head_dim": 128, "mlp_dim": 2048, "num_experts": 32,
    "experts_held": 8, "moe_top_k": 4,
}
HAND_CELL = {"batch": 8, "seq": 2048, "moe": True}
ITS_ATTENTION = '{k: v * model["layers"].count("*") for k, v in one.items()}'
EVERY_LAYERS = '{k: v * model["num_layers"] for k, v in one.items()}'


def _family(tmp_path, name, attention=ITS_ATTENTION, grouped="grouped"):
    """The hand-made family as ``<name>.py`` beside a ``configs/``, and the
    configuration that names it."""
    (tmp_path / f"{name}.py").write_text(
        FAMILY.replace("ATTENTION", attention).replace("GROUPED", grouped))
    (tmp_path / "configs").mkdir(exist_ok=True)
    with open(os.path.join(HERE, "rehearsal", "configs", "toy.json")) as f:
        config = dict(json.load(f), model=MODEL, flops=name)
    (tmp_path / "configs" / f"{name}.json").write_text(json.dumps(config))
    return harness.load_config(name, str(tmp_path))


def _hand_trace(attention_s, grouped_s, steps=20):
    rows = [
        {"name": "%flash_attn_fused_fwd.4", "count": steps,
         "total_s": 0.25 * attention_s, "self_s": 0.25 * attention_s,
         "about": CALL},
        {"name": "%flash_attn_fused_bwd.5", "count": steps,
         "total_s": 0.75 * attention_s, "self_s": 0.75 * attention_s,
         "about": CALL},
        {"name": "%ragged-dot-none.6", "count": 12 * steps,
         "total_s": grouped_s, "self_s": grouped_s, "about": CALL},
    ]
    return {"steps": steps, "devices": [{"ops": rows, "steps": steps}]}


def _hand_worked(steps=20):
    """Least seconds of ``steps`` steps, worked here and not by the module:
    ONE attention layer of 4 heads of 128 over 8 rows of 2048, six matmuls
    of 2 * T * T * D / 2; TWO sparse layers whose 8 held experts get a
    quarter of the 8 * 2048 * 4 assignments, two projections, each one
    matmul forward and two backward. Both are bound by the matrix unit."""
    attention = 6 * 2048 * 2048 * 128 * 8 * 4
    rows = 8 * 2048 * 4 // 4
    grouped = 2 * (2 * 3 * 2 * rows * 1024 * 2048)
    return (steps * attention / 197e12, steps * grouped / 197e12)


def test_a_family_of_unlike_layers_reads_its_hand_worked_share(
        tmp_path, capsys):
    config = _family(tmp_path, "flops_handmade")
    hook = harness.load_hook("flops_handmade", config, str(tmp_path))
    least_attention, least_grouped = _hand_worked()
    run = _run(config, HAND_CELL,
               _hand_trace(2 * least_attention, 2.5 * least_grouped), hook)
    got = harness.read_layer_metrics(_readers(), run)
    assert got["kernel.attn_roofline"] == (pytest.approx(50.0), "%")
    assert got["kernel.moe_gmm_roofline"] == (pytest.approx(40.0), "%")
    printed = harness.json_lines(capsys.readouterr().out)
    assert [ln["roofline"]["bound"] for ln in printed] == ["flops", "flops"]
    # what PR 35 repaired: the parent's readers count three attention
    # layers of 4 heads of 1024 // 4 = 256 for one of 4 heads of 128 (six
    # times the operations: impossible shares), and three sparse layers
    # with all 32 experts' rows for two with a quarter of them
    assert _parents_share(run, "kernel.attn_roofline") == pytest.approx(
        3 * (256 / 128) * 50.0)
    assert _parents_share(run, "kernel.moe_gmm_roofline") == pytest.approx(
        (3 / 2) * (32 / 8) * 40.0)
    counted = hook.count(MODEL, 2048)
    assert counted["active_params"] < counted["params"]


@pytest.mark.parametrize("kind,left_out", [
    ("attention", "kernel.attn_roofline"),
    ("grouped_matmul", "kernel.moe_gmm_roofline"),
])
def test_a_kind_the_family_does_not_run_is_left_out(
        tmp_path, kind, left_out):
    config = _family(tmp_path, "flops_without", **{
        "attention": {"attention": "None"},
        "grouped_matmul": {"grouped": "None"},
    }[kind])
    hook = harness.load_hook("flops_without", config, str(tmp_path))
    assert hook.step_work(MODEL, 8, 2048)[kind] is None
    least_attention, least_grouped = _hand_worked()
    # the trace holds both kinds of kernel all the same
    run = _run(config, HAND_CELL,
               _hand_trace(2 * least_attention, 2 * least_grouped), hook)
    got = harness.read_layer_metrics(_readers(), run)
    (other,) = set(READERS) - {left_out}
    assert set(got) == {other}
    assert got[other][0] == pytest.approx(50.0)


def test_work_the_program_does_not_run_is_refused_by_the_ceiling(
        tmp_path, monkeypatch, capsys):
    """The family counts all three layers as attention layers; the trace
    holds the kernels of one, at 50 % of their roofline: 150 %."""
    config = _family(tmp_path, "flops_overcount", attention=EVERY_LAYERS)
    hook = harness.load_hook("flops_overcount", config, str(tmp_path))
    least_attention, least_grouped = _hand_worked()
    run = _run(config, HAND_CELL,
               _hand_trace(2 * least_attention, 2 * least_grouped), hook)
    with pytest.raises(harness.Refused, match="kernel.attn_roofline") as e:
        harness.read_layer_metrics(_readers(), run)
    capsys.readouterr()
    detail = e.value.detail
    assert detail["metric"] == "kernel.attn_roofline"
    assert detail["value"] == pytest.approx(150.0)
    assert detail["ceiling"] == 100.0

    # and through main: rc 3, the numbers in the note, no result line
    def run_cell(*a, **k):
        return harness.read_layer_metrics(_readers(), run)

    monkeypatch.setattr(harness, "run_cell", run_cell)
    rc = harness.main(
        ["--workload", "x", "--seed", "1", "--seconds", "1", "--trace", "1"])
    out = harness.json_lines(capsys.readouterr().out)
    assert rc == 3 and not any("correct" in ln for ln in out)
    last = out[-1]
    assert "above its ceiling" in last["refused"]
    assert last["metric"] == "kernel.attn_roofline"
    assert last["value"] == pytest.approx(150.0)
    assert last["steps_traced"] == 20
    found = last["reader_printed"][0]
    assert found["attention_kernels"]["seconds"] == pytest.approx(
        2 * least_attention)
    assert found["roofline"]["seconds"] == pytest.approx(3 * least_attention)
    assert last["step_work_counted"]["attention"]["flops"] == (
        3 * 6 * 2048 * 2048 * 128 * 8 * 4)
    # exactly at the ceiling is a share the chip could reach
    at_it = _run(config, HAND_CELL,
                 _hand_trace(3 * least_attention, 2 * least_grouped), hook)
    monkeypatch.undo()
    assert harness.read_layer_metrics(_readers(), at_it)[
        "kernel.attn_roofline"][0] == pytest.approx(100.0, abs=1e-9)


# -- what load_config and run_cell refuse before anything runs ----------------
def test_a_family_module_that_is_missing_or_thin_is_refused(tmp_path):
    config = _family(tmp_path, "flops_handmade")
    assert config["flops"] == "flops_handmade"
    path = tmp_path / "configs" / "flops_handmade.json"
    for module, word in (("flops_nowhere", "no family module flops_nowhere"),
                         ("../flops", "names no module"),
                         ("", "names no module")):
        path.write_text(json.dumps(dict(config, flops=module)))
        with pytest.raises(harness.Refused, match=word) as e:
            harness.load_config("flops_handmade", str(tmp_path))
        assert "flops_handmade" in str(e.value)
    (tmp_path / "flops_thin.py").write_text(
        FAMILY.replace("def count(", "def counted("))
    path.write_text(json.dumps(dict(config, flops="flops_thin")))
    with pytest.raises(harness.Refused, match=r"lacks .*'count'") as e:
        harness.load_config("flops_handmade", str(tmp_path))
    assert "flops_thin.py" in str(e.value)
    (tmp_path / "flops_broken.py").write_text("def count(:\n")
    path.write_text(json.dumps(dict(config, flops="flops_broken")))
    with pytest.raises(harness.Refused, match="SyntaxError"):
        harness.load_config("flops_handmade", str(tmp_path))
    # a module here is found from any data directory, as references are;
    # absent, the key means flops
    path.write_text(json.dumps(dict(config, flops="flops_moe")))
    assert harness.load_hook(
        "x", harness.load_config("flops_handmade", str(tmp_path)),
        str(tmp_path)).__file__ == os.path.join(BENCH, "flops_moe.py")
    dense = _config("gpt2-124m")
    assert "flops" not in dense
    assert harness.load_hook("gpt2-124m", dense).__file__ == os.path.join(
        BENCH, "flops.py")


@pytest.mark.parametrize("body,word", [
    ("raise KeyError('layers')", "cannot count the model"),
    ("return {'params': 1, 'active_params': 1}", "cannot count the model"),
    ("return {'params': 0, 'active_params': 0,"
     " 'train_flops_per_token': float('nan')}", "another shape"),
])
def test_a_family_that_cannot_count_the_model_costs_no_run(
        tmp_path, monkeypatch, body, word):
    """``run_cell`` asks the hook before it starts the launcher."""
    monkeypatch.setattr(harness, "Chain", None)  # starting one: TypeError
    here = os.path.join(HERE, "rehearsal")
    shutil.copytree(os.path.join(here, "cells"), tmp_path / "cells")
    (tmp_path / "configs").mkdir()
    (tmp_path / "flops_cannot.py").write_text(
        f"def count(model, seq):\n    {body}\n\n\n"
        "def step_work(model, batch, seq):\n"
        "    return {'attention': None, 'grouped_matmul': None}\n")
    with open(os.path.join(here, "configs", "toy.json")) as f:
        config = dict(json.load(f), flops="flops_cannot")
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(config))
    with pytest.raises(harness.Refused, match=word) as e:
        harness.run_cell(
            "toy.steady", 1, 0.5, False, device_spec="cpu:1",
            expect_platform="cpu", data_dir=str(tmp_path))
    assert "flops_cannot.py" in str(e.value)
