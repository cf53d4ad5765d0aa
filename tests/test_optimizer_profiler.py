"""Resource optimizer, strategy generator and profiler."""

import numpy as np
import pytest

from dlrover_tpu.common import comm
from dlrover_tpu.master.paral_config import ParalConfigService
from dlrover_tpu.master.resource.optimizer import (
    JobResourceOptimizer,
    ResourcePlan,
)
from dlrover_tpu.models import gpt2_small, tiny
from dlrover_tpu.accel.profiler import (
    chip_peak_tflops,
    profile_model,
)


def _sample(nodes, sps, mem=1000):
    return comm.JobMetricsSample(
        timestamp=0.0,
        alive_nodes=nodes,
        steps_per_sec=sps,
        total_memory_mb=mem,
    )


class TestResourceOptimizer:
    def test_diminishing_returns_recommends_scale_down(self):
        opt = JobResourceOptimizer(min_speedup_per_unit=0.6)
        opt.observe(_sample(4, 10.0))
        opt.observe(_sample(8, 11.0))  # 2x nodes, 1.1x speed: bad deal
        plan = opt.generate_plan()
        assert plan.worker_count == 4
        assert "recommend 4" in plan.reason

    def test_good_scaling_keeps_size(self):
        opt = JobResourceOptimizer(min_speedup_per_unit=0.6)
        opt.observe(_sample(4, 10.0))
        opt.observe(_sample(8, 18.0))  # 1.8x of linear 2x: fine
        plan = opt.generate_plan()
        assert plan.worker_count is None

    def test_memory_rightsizing_and_oom(self):
        class _Coll:
            def snapshot(self):
                return comm.JobMetrics(
                    samples=[_sample(2, 5.0, mem=4000)]
                )

        opt = JobResourceOptimizer(
            metric_collector=_Coll(), memory_headroom=1.5
        )
        plan = opt.generate_plan()
        assert plan.worker_memory_mb == 3000  # 4000/2 * 1.5
        oom = opt.generate_oom_recovery_plan(2048)
        assert oom.worker_memory_mb == 4096

    def test_brain_seam_wins(self):
        opt = JobResourceOptimizer(
            brain=lambda samples: ResourcePlan(
                worker_count=16, reason="cluster"
            )
        )
        assert opt.generate_plan().worker_count == 16

    def test_autoscaler_runs_optimizer_plan(self):
        from dlrover_tpu.master.local_master import LocalJobMaster
        from dlrover_tpu.master.scaler import CallbackScaler

        scaler = CallbackScaler(lambda p: None)
        master = LocalJobMaster(node_num=4, scaler=scaler)
        from dlrover_tpu.common.constants import NodeStatus

        for i in range(4):
            node = master.job_manager.get_node("worker", i)
            node.update_status(NodeStatus.RUNNING)
        opt = JobResourceOptimizer()
        opt.observe(_sample(2, 10.0))
        opt.observe(_sample(4, 11.0))
        master.auto_scaler._optimizer = opt
        master.auto_scaler.run_optimization_pass()
        assert len(master.auto_scaler.alive_nodes()) == 2


class TestStrategyGenerator:
    def test_suggest_from_node_resources(self):
        svc = ParalConfigService()
        cfg = svc.suggest_initial_config(
            batch_size=8, node_cpu=16, node_memory_mb=32000,
            used_memory_mb=8000,
        )
        assert cfg.dataloader.num_workers == 8  # half the cores
        assert cfg.dataloader.batch_size == 24  # 3x headroom
        # capped at 4x
        cfg = svc.suggest_initial_config(
            batch_size=8, node_cpu=4, node_memory_mb=100000,
            used_memory_mb=1000,
        )
        assert cfg.dataloader.batch_size == 32

    def test_passthrough_without_resources(self):
        svc = ParalConfigService()
        cfg = svc.suggest_initial_config(batch_size=8, num_workers=3)
        assert cfg.dataloader.batch_size == 8
        assert cfg.dataloader.num_workers == 3


class TestProfiler:
    def test_gpt2_param_count_matches(self):
        import jax

        from dlrover_tpu.models import init_params

        cfg = tiny()
        prof = profile_model(cfg, batch=4, seq=32)
        params = init_params(jax.random.PRNGKey(0), cfg)
        real = sum(
            int(np.prod(x.shape))
            for x in jax.tree_util.tree_leaves(params)
        )
        # analytic count ignores norm scales (tiny contribution)
        assert abs(prof.total_params - real) / real < 0.01

    def test_flops_scale_with_tokens(self):
        cfg = gpt2_small()
        p1 = profile_model(cfg, batch=1, seq=128)
        p2 = profile_model(cfg, batch=2, seq=128)
        # attention term is superlinear in seq but linear in batch
        assert p2.fwd_flops == pytest.approx(2 * p1.fwd_flops)
        assert "TOTAL" in p1.report()

    def test_gpt2_step_flops_sane(self):
        """6·N·D rule cross-check: GPT-2 124M @ 1024 tokens ≈ 0.88
        TFLOPs/sequence fwd+bwd (±30% for attention/head terms)."""
        cfg = gpt2_small()
        prof = profile_model(cfg, batch=1, seq=1024)
        six_nd = 6.0 * prof.total_params * 1024
        assert prof.step_flops == pytest.approx(six_nd, rel=0.5)


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform = platform
        self.device_kind = device_kind


@pytest.mark.parametrize(
    "kind, peak",
    [("TPU v5 lite", 197.0), ("TPU v5p", 459.0), ("TPU v4", 275.0)],
)
def test_chip_peak_known_tpu_kinds(kind, peak):
    assert chip_peak_tflops(_FakeDevice("tpu", kind)) == peak


def test_chip_peak_is_none_off_the_tpu_and_an_error_for_an_unknown_tpu():
    """A CPU run has no MFU; a TPU the table does not know must not
    silently turn MFU into None."""
    assert chip_peak_tflops(_FakeDevice("cpu", "cpu")) is None
    with pytest.raises(KeyError, match="TPU v9"):
        chip_peak_tflops(_FakeDevice("tpu", "TPU v9 mega"))
