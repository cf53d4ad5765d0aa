"""Elastic agent end-to-end tests: real master + real agent + real worker
subprocesses on localhost (parity with the reference's
test_elastic_training_agent.py pattern)."""

import os
import subprocess
import sys
import threading
import time

import pytest

from dlrover_tpu.agent.master_client import MasterClient
from dlrover_tpu.agent.training_agent import (
    ElasticTrainingAgent,
    WorkerSpec,
    WorkerState,
)
from dlrover_tpu.master.local_master import start_local_master

ASSETS = os.path.join(os.path.dirname(__file__), "assets")


@pytest.fixture()
def master():
    m = start_local_master(node_num=1)
    for mgr in m.rdzv_managers.values():
        mgr.update_rdzv_params(min_nodes=1, max_nodes=1, waiting_timeout=0)
    yield m
    m.stop()


def _make_agent(master, entrypoint, **spec_kw):
    client = MasterClient(master.addr, node_id=0)
    spec = WorkerSpec(
        entrypoint=os.path.join(ASSETS, entrypoint),
        nproc_per_node=spec_kw.pop("nproc_per_node", 1),
        max_restarts=spec_kw.pop("max_restarts", 2),
        monitor_interval=0.2,
        **spec_kw,
    )
    return ElasticTrainingAgent(node_rank=0, spec=spec, client=client)


class TestAgent:
    def test_success(self, master):
        agent = _make_agent(master, "exit0.py")
        result = agent.run()
        assert result.state == WorkerState.SUCCEEDED
        assert result.restarts == 0

    def test_restart_then_success(self, master):
        agent = _make_agent(master, "fail_once.py")
        result = agent.run()
        assert result.state == WorkerState.SUCCEEDED
        assert result.restarts == 1
        # the failure was reported to the master
        node = master.job_manager.get_node("worker", 0)

    def test_restart_budget_exhausted(self, master):
        agent = _make_agent(master, "fail_always.py", max_restarts=1)
        result = agent.run()
        assert result.state == WorkerState.FAILED
        assert result.restarts == 1
        assert "exitcode=3" in result.message

    def test_save_at_breakpoint_hook(self, master):
        agent = _make_agent(master, "fail_once.py")
        calls = []
        agent.set_checkpoint_hook(lambda: calls.append(1))
        result = agent.run()
        assert result.state == WorkerState.SUCCEEDED
        assert calls == [1]  # hook ran before the restart


class TestLauncher:
    def test_run_cli_single_proc(self, master):
        """dlrover-tpu-run against an existing master."""
        from dlrover_tpu.trainer import run as run_mod

        rc = run_mod.main(
            [
                "--nnodes=1",
                "--nproc-per-node=1",
                f"--master-addr={master.addr}",
                "--monitor-interval=0.2",
                os.path.join(ASSETS, "exit0.py"),
            ]
        )
        assert rc == 0

    @pytest.mark.slow
    def test_run_cli_distributed_training(self, master):
        """2 JAX processes rendezvous via master and psum across."""
        from dlrover_tpu.trainer import run as run_mod

        rc = run_mod.main(
            [
                "--nnodes=1",
                "--nproc-per-node=2",
                f"--master-addr={master.addr}",
                "--monitor-interval=0.5",
                "--device-spec=cpu:1",
                os.path.join(ASSETS, "toy_train.py"),
            ]
        )
        assert rc == 0

    @pytest.mark.slow
    def test_flash_ckpt_survives_preemption(self, master, tmp_path):
        """Worker flash-saves to memory only and dies hard at step 3; the
        agent persists shm before restarting, and the restarted worker
        resumes from step 3 (whole-stack Flash Checkpoint)."""
        from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
        from dlrover_tpu.trainer import run as run_mod

        AsyncCheckpointSaver.reset()
        ckpt_dir = str(tmp_path / "flash")
        os.environ["TEST_CKPT_DIR"] = ckpt_dir
        try:
            rc = run_mod.main(
                [
                    "--nnodes=1",
                    "--nproc-per-node=1",
                    f"--master-addr={master.addr}",
                    "--monitor-interval=0.3",
                    "--device-spec=cpu:1",
                    os.path.join(ASSETS, "ckpt_train.py"),
                ]
            )
        finally:
            os.environ.pop("TEST_CKPT_DIR", None)
            AsyncCheckpointSaver.reset()
        assert rc == 0


def test_enable_compile_cache(tmp_path, monkeypatch):
    import jax

    from dlrover_tpu.trainer.elastic import distributed
    from dlrover_tpu.trainer.elastic.distributed import enable_compile_cache

    before = jax.config.jax_compilation_cache_dir
    try:
        # placed from outside: JAX's own variable names the directory
        # and the program sets no other
        monkeypatch.delenv("DLROVER_TPU_COMPILE_CACHE", raising=False)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "x"))
        assert enable_compile_cache() == str(tmp_path / "x")
        assert jax.config.jax_compilation_cache_dir == before

        # not placed: one fixed directory inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        monkeypatch.setattr(
            distributed, "framework_root", lambda: str(tmp_path)
        )
        got = enable_compile_cache()
        assert got == str(tmp_path / ".compile_cache")
        assert (tmp_path / ".compile_cache").is_dir()
        assert jax.config.jax_compilation_cache_dir == got
        assert enable_compile_cache() == got  # same path every call

        monkeypatch.setenv("DLROVER_TPU_COMPILE_CACHE", "off")
        assert enable_compile_cache() == ""
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_auto_configure(monkeypatch):
    from dlrover_tpu.trainer.run import auto_configure, parse_args

    monkeypatch.setenv("DLROVER_TPU_NODE_NUM", "4")
    args = parse_args(
        ["--auto-config", "--device-spec=cpu:8", "tests/assets/exit0.py"]
    )
    args = auto_configure(args)
    assert args.nnodes == "4"
    assert args.nproc_per_node == 8  # cpu:8 spec => static count
    assert args.network_check  # >= 4 nodes turns the check on

    monkeypatch.setenv("DLROVER_TPU_NODE_NUM", "2")
    args = parse_args(
        ["--auto-config", "--device-spec=cpu:2", "tests/assets/exit0.py"]
    )
    args = auto_configure(args)
    assert args.nnodes == "2" and not args.network_check

    # on TPU one worker per host owns all of the host's chips
    for spec in ("--device-spec=tpu", "--nproc-per-node=4"):
        args = parse_args(["--auto-config", spec, "tests/assets/exit0.py"])
        assert auto_configure(args).nproc_per_node == 1

    # no platform env, CLI-provided --nnodes=8: the gate must fire off
    # the parsed min_nodes, not only the env-derived node count
    monkeypatch.delenv("DLROVER_TPU_NODE_NUM", raising=False)
    args = parse_args(
        [
            "--auto-config", "--nnodes=8", "--device-spec=cpu:2",
            "tests/assets/exit0.py",
        ]
    )
    args = auto_configure(args)
    assert args.network_check


def test_device_spec_tpu_is_an_error_off_the_tpu():
    """Asking for the chip and coming up on anything else fails before
    anything is built (no quiet drop to the CPU)."""
    from dlrover_tpu.utils.device import check_devices, configure_devices

    configure_devices("tpu")  # configuration only: touches no device
    with pytest.raises(RuntimeError, match="asks for a TPU"):
        check_devices("tpu")
    check_devices("cpu:8")
    with pytest.raises(ValueError, match="unknown device spec"):
        configure_devices("gpu:1")


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_init_elastic_joins_the_world_before_it_touches_a_device(
    monkeypatch, platform
):
    """``jax.distributed.initialize`` refuses to run once the backend is
    up, so with spec ``tpu`` in a multi-process world nothing may ask
    for the devices before it; the platform is asserted after. A second
    call (the trainer's, after the script's) does nothing."""
    import types

    import jax

    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.trainer.elastic import distributed
    from dlrover_tpu.utils import device

    calls = []
    monkeypatch.setattr(
        jax, "devices",
        lambda *a: calls.append("devices")
        or [types.SimpleNamespace(platform=platform)],
    )
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda **kw: calls.append(("initialize", kw)),
    )
    monkeypatch.setattr(
        distributed, "enable_compile_cache",
        lambda: calls.append("cache"),
    )
    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setenv(device.DEVICE_SPEC_ENV, "tpu")
    monkeypatch.setenv(NodeEnv.NUM_PROCESSES, "2")
    monkeypatch.setenv(NodeEnv.PROCESS_ID, "1")
    monkeypatch.setenv(NodeEnv.COORDINATOR_ADDR, "localhost:1234")
    if platform == "cpu":
        with pytest.raises(RuntimeError, match="asks for a TPU"):
            distributed.init_elastic()
        assert not distributed._initialized
    else:
        ctx = distributed.init_elastic()
        assert ctx.is_distributed and ctx.process_id == 1
        assert distributed._initialized
        distributed.init_elastic()  # once per process
    assert [c if isinstance(c, str) else c[0] for c in calls] == [
        "cache", "initialize", "devices",
    ]
    assert calls[1][1]["coordinator_address"] == "localhost:1234"
    assert calls[1][1]["num_processes"] == 2


def test_pallas_interpret_mode_never_guesses(monkeypatch):
    """Off the TPU the kernels interpret; a backend that fails to come
    up raises instead of reading as 'not a TPU'."""
    import jax

    from dlrover_tpu.common.jax_compat import pallas_interpret_mode

    monkeypatch.delenv("DLROVER_TPU_PALLAS", raising=False)
    assert pallas_interpret_mode() is True
    monkeypatch.setenv("DLROVER_TPU_PALLAS", "compile")
    assert pallas_interpret_mode() is False
    monkeypatch.delenv("DLROVER_TPU_PALLAS")

    def boom():
        raise RuntimeError("no backend")

    monkeypatch.setattr(jax, "devices", boom)
    with pytest.raises(RuntimeError, match="no backend"):
        pallas_interpret_mode()


def test_node_check_collective_leg_runs_on_one_process_with_many_devices(
    monkeypatch,
):
    """One process owning several devices (a whole TPU host) still
    exercises the interconnect: the leg is gated on the device count,
    not on the process count."""
    from dlrover_tpu.trainer.node_check import tpu_check

    calls = []
    monkeypatch.setattr(
        tpu_check, "matmul_rounds", lambda r, s: calls.append("mm") or 0.5
    )
    monkeypatch.setattr(
        tpu_check,
        "collective_rounds",
        lambda r, e: calls.append("coll") or 0.25,
    )
    written = []
    monkeypatch.setattr(tpu_check, "write_result", written.append)
    monkeypatch.setenv("DLROVER_TPU_COMPILE_CACHE", "off")
    assert tpu_check.main() == 0  # 8 virtual devices, one process
    assert calls == ["mm", "coll"] and written == [0.75]


def test_node_check_refuses_an_unknown_platform(monkeypatch):
    import jax

    from dlrover_tpu.trainer.node_check import tpu_check

    class _Dev:
        platform = "gpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [_Dev()])
    with pytest.raises(RuntimeError, match="not 'gpu'"):
        tpu_check._workload_scale()
