"""``chip_smoke.py`` at toy size on the CPU backend: the same phase
functions ``main`` runs on the chip (launcher, node check, train, flash
save, one hard kill, shm restore, checks), so that a later PR cannot
break the script unseen. This route can never print the TPU line."""

import contextlib
import io
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402


def _toy_spec(**kw):
    kw = {"device_spec": "cpu:1", **kw}
    return chip_smoke.SmokeSpec(
        model="tiny",
        model_overrides={"max_seq_len": 32},
        batch=4,
        seq=32,
        steps=14,
        save_interval=5,
        lr=3e-3,
        expect_platform="cpu",
        timeout_s=300,
        **kw,
    )


def _run(spec, run_dir):
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = chip_smoke.run_smoke(spec, str(run_dir))
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    return result, lines


@pytest.fixture(scope="module")
def toy(tmp_path_factory):
    mp = pytest.MonkeyPatch()
    # the persistent cache, placed from outside, as the chip tool may
    mp.setenv(
        "JAX_COMPILATION_CACHE_DIR",
        str(tmp_path_factory.mktemp("jaxcache")),
    )
    try:
        yield _run(_toy_spec(), tmp_path_factory.mktemp("smoke"))
    finally:
        mp.undo()


def test_every_phase_passes_in_order(toy):
    result, lines = toy
    assert result == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1},
    }
    phases = [ln for ln in lines if "phase" in ln]
    assert [p["phase"] for p in phases] == [
        "launcher", "node_check", "train", "save", "kill", "restore",
        "checks",
    ]
    assert all(p["passed"] for p in phases)


def test_one_kill_then_restore_from_memory_and_equal_replay(toy):
    _, lines = toy
    by = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert by["kill"]["rc"] == 137
    k = by["restore"]["restored_step"]
    assert k == 5 and by["restore"]["launcher_rc"] == 0
    assert by["restore"]["restore_seconds"] > 0
    checks = by["checks"]
    # the tolerance is printed on an earlier line than the comparison
    tol = [i for i, ln in enumerate(lines) if "replay_tolerance_abs" in ln]
    assert tol and tol[0] < lines.index(checks)
    assert lines[tol[0]]["replay_tolerance_abs"] == 0.0
    assert checks["replayed_steps"] and checks["replay_max_abs_diff"] == 0.0
    # the restored params and Adam moments are what was staged
    assert set(checks["restored_state_digest"]) == {"params", "opt_state"}
    assert checks["restored_state_digest"] == checks["staged_state_digest"]
    assert checks["losses_second_from_step"] == k + 1
    assert checks["loss_end"] < checks["loss_step_1"]


def test_second_incarnation_is_served_from_the_persistent_cache(toy):
    _, lines = toy
    by = {ln["phase"]: ln for ln in lines if "phase" in ln}
    first = by["kill"]["first_incarnation_persistent_cache"]
    second = by["restore"]["second_incarnation_persistent_cache"]
    assert first["misses"] >= 1  # the first one wrote the entries
    # hits, not "no miss": only compiles above 0.5 s are cached, so on a
    # loaded host the second incarnation can write an entry the first
    # compiled too fast to keep
    assert second["hits"] >= 1
    assert by["restore"]["second_incarnation_compile_seconds"] > 0


def test_ok_only_on_the_tpu(toy):
    """Every phase passed, but on the CPU: the last line's ``ok`` is
    false and the exit code is not 0."""
    result, _ = toy
    assert result["ok"]
    final = chip_smoke.final_result(dict(result), chips=1)
    assert final["ok"] is False
    assert final["device"]["platform"] == "cpu"
    on_tpu = {"ok": True, "device": {"platform": "tpu", "kind": "x", "count": 1}}
    assert chip_smoke.final_result(dict(on_tpu), chips=1)["ok"] is True
    assert chip_smoke.final_result(dict(on_tpu), chips=4)["ok"] is False


def test_a_failed_phase_names_itself_and_carries_the_logs(tmp_path):
    """Restart budget 0 makes the kill final: the result is false, the
    failing phase is named, and the worker log's tail comes before it."""
    result, lines = _run(_toy_spec(max_restarts=0), tmp_path)
    assert result["ok"] is False
    assert result["failed_phase"] == "restore"
    failed = [ln for ln in lines if ln.get("phase") == "restore"]
    assert failed and failed[0]["passed"] is False and failed[0]["error"]
    logs = {ln["log"]: ln["tail"] for ln in lines if "log" in ln}
    assert "launcher.log" in logs and "worker_0_0_r0.log" in logs
    assert any("hard exit(137)" in ln for ln in logs["worker_0_0_r0.log"])
    assert any("worker failure" in ln for ln in logs["launcher.log"])
    # the logs come after the failing phase's line, before the last line
    assert lines.index(failed[0]) < min(
        i for i, ln in enumerate(lines) if "log" in ln
    )


def _evidence(**restore):
    """What a chain that went well leaves, for ``check_chain`` alone."""
    digest = {"params": "00000001", "opt_state": "00000002"}
    row = {"stage_commits": 1, "state_digest": None}
    return {
        "restored_step": 2,
        "steps0": [
            {**row, "step": 1, "loss": 3.0},
            {**row, "step": 2, "loss": 2.5, "state_digest": digest},
            {**row, "step": 3, "loss": 2.0},
        ],
        "steps1": [
            {**row, "step": 3, "loss": 2.0},
            {**row, "step": 4, "loss": 1.5},
        ],
        "worker": {
            "platform": "cpu", "kind": "cpu", "count": 1,
            "restore": {"seconds": 0.1, "step": 2, "digest": digest,
                        **restore},
            "program": {"tpu_custom_call": False},
            "persistent_cache": {"hits": 1, "misses": 0},
            "state_bytes": 1, "strategy": "dp1",
        },
    }


def test_a_restored_state_that_is_not_what_was_staged_fails_the_checks():
    """Equal losses do not cover the Adam moments; the checksums do."""
    spec = chip_smoke.SmokeSpec(steps=4, expect_platform="cpu")
    with contextlib.redirect_stdout(io.StringIO()):
        assert chip_smoke.check_chain(spec, _evidence())["platform"] == "cpu"
        damaged = {"params": "00000001", "opt_state": "0badbad0"}
        for digest in (damaged, None):
            with pytest.raises(chip_smoke.PhaseFailed, match="not what was"):
                chip_smoke.check_chain(spec, _evidence(digest=digest))


def test_a_fault_of_the_script_still_ends_in_the_last_line(
    tmp_path, monkeypatch
):
    """Not a phase's verdict but an exception nobody planned for (a
    report the kill cut short): the result is false, the phase is
    ``internal``, the exception is named and the logs come along."""

    def cut_short(spec, run_dir):
        os.makedirs(os.path.join(run_dir, "logs"))
        with open(os.path.join(run_dir, "launcher.log"), "w") as f:
            f.write("the launcher's last words\n")
        return {"worker": {}}["steps0"]

    monkeypatch.setattr(chip_smoke, "run_chain", cut_short)
    result, lines = _run(_toy_spec(), tmp_path / "run")
    assert result["ok"] is False and result["failed_phase"] == "internal"
    failed = [ln for ln in lines if ln.get("phase") == "internal"]
    assert failed and "KeyError('steps0')" in failed[0]["error"]
    logs = {ln["log"]: ln["tail"] for ln in lines if "log" in ln}
    assert logs["launcher.log"] == ["the launcher's last words"]
    assert chip_smoke.final_result(result, chips=1)["ok"] is False


def test_sharded_path_on_four_virtual_devices(tmp_path, monkeypatch):
    """``--chips 4`` at toy size: one worker owning four devices, state
    sharded over all of them (fsdp=4), kill, restore of the sharded
    state from shm, and the one-device run it is compared with."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    spec = _toy_spec(
        device_spec="cpu:4", expect_devices=4, mesh={"fsdp": 4}
    )
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        result = chip_smoke.run_smoke(
            spec, str(tmp_path / "run"), sharded_atol=0.05
        )
    lines = [json.loads(ln) for ln in out.getvalue().splitlines()]
    by = {ln["phase"]: ln for ln in lines if "phase" in ln}
    assert result["ok"], lines[-8:]
    assert result["device"]["count"] == 4
    assert by["restore"]["restored_step"] == 5  # from memory, sharded
    assert by["checks"]["strategy"].startswith("fsdp4")
    sharded = by["sharded"]
    assert sharded["max_abs_loss_diff"] <= 0.05
    assert len(sharded["spreads"]) == 3  # the param, mu and nu
    for sp in sharded["spreads"]:
        assert sp["devices"] == [0, 1, 2, 3]
        assert sp["shard_bytes"] == [sp["total_bytes"] // 4] * 4


def test_chip_hidden_exits_nonzero_without_ok():
    """The driver's own check, here: ``python chip_smoke.py`` where JAX
    finds no accelerator fails and prints no ``"ok": true``."""
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300,
    )
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    last = json.loads(p.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["failed_phase"] == "node_check"


def test_the_control_side_imports_no_jax():
    """The smoke's parent, the launcher, the agent, the saver and the
    master stay off JAX: a parent that has touched JAX holds the chip."""
    code = (
        "import sys; sys.path.insert(0, %r); "
        "import chip_smoke, dlrover_tpu.trainer.run, "
        "dlrover_tpu.agent.training_agent, "
        "dlrover_tpu.agent.node_check_agent, dlrover_tpu.agent.aggregator, "
        "dlrover_tpu.ckpt.saver, dlrover_tpu.master.main, "
        "dlrover_tpu.master.local_master; "
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith(('jax.', 'jaxlib'))]; "
        "assert not bad, bad" % REPO
    )
    p = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True
    )
    assert p.returncode == 0, p.stderr[-2000:]
