"""Device prefetcher + pipelined transfer machinery.

Covers the ISSUE-1 contracts: ordering, exhaustion, exception
propagation, buffer drop + re-prime on a simulated elastic resize, the
checkpoint rewind accounting, and the pipeline stats record.
"""

import time

import jax
import numpy as np
import pytest

from dlrover_tpu.accel.profiler import PipelineStats
from dlrover_tpu.data.prefetch import DevicePrefetcher, sharded_placement


def _batches(n, size=8):
    for i in range(n):
        yield np.full((size,), i, np.float32)


class TestDevicePrefetcher:
    def test_ordering_and_exhaustion(self):
        p = DevicePrefetcher(_batches(10), depth=2)
        try:
            got = [int(np.asarray(b)[0]) for b in p]
            assert got == list(range(10))
            # exhausted: every further next() keeps raising
            with pytest.raises(StopIteration):
                next(p)
            with pytest.raises(StopIteration):
                next(p)
            s = p.stats
            assert s.prefetch_hits + s.prefetch_misses == 10
        finally:
            p.close()

    def test_batches_are_device_placed(self):
        p = DevicePrefetcher(_batches(3))
        try:
            for b in p:
                assert isinstance(b, jax.Array)
        finally:
            p.close()

    def test_pytree_batches(self):
        def gen():
            for i in range(4):
                yield {"x": np.full((4,), i), "y": (np.arange(2), i)}

        p = DevicePrefetcher(gen())
        try:
            out = list(p)
            assert len(out) == 4
            assert int(np.asarray(out[2]["x"])[0]) == 2
            assert out[3]["y"][1] == 3
        finally:
            p.close()

    def test_exception_propagates_after_good_batches(self):
        def gen():
            yield np.zeros(4)
            yield np.ones(4)
            raise RuntimeError("producer exploded")

        p = DevicePrefetcher(gen(), depth=2)
        try:
            assert int(np.asarray(next(p))[0]) == 0
            assert int(np.asarray(next(p))[0]) == 1
            with pytest.raises(RuntimeError, match="producer exploded"):
                next(p)
            # the error is terminal and sticky, not swallowed
            with pytest.raises(RuntimeError, match="producer exploded"):
                next(p)
        finally:
            p.close()

    def test_reprime_drops_device_copies_keeps_samples(self):
        """Simulated elastic resize: 8-device placement shrinks to 4.
        The buffered device batches are dropped and re-placed under the
        new sharding — order preserved, nothing lost."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        devs = jax.devices()
        assert len(devs) >= 8, "conftest pins an 8-device CPU mesh"
        mesh8 = Mesh(np.array(devs[:8]).reshape(8), ("dp",))
        mesh4 = Mesh(np.array(devs[:4]).reshape(4), ("dp",))

        def place(mesh):
            sh = NamedSharding(mesh, P("dp"))
            return lambda b: jax.device_put(b, sh)

        p = DevicePrefetcher(_batches(6), placement=place(mesh8), depth=4)
        try:
            first = next(p)
            assert len(first.sharding.device_set) == 8
            # let the producer fill the buffer before the "resize"
            deadline = time.time() + 5
            while p.buffered_batches() < 4 and time.time() < deadline:
                time.sleep(0.01)
            n = p.reprime(place(mesh4))
            assert n >= 1
            rest = list(p)
            order = [int(np.asarray(b)[0]) for b in [first] + rest]
            assert order == list(range(6))  # no sample lost, in order
            # the re-placed (previously buffered) batches carry the new
            # world's sharding
            assert all(
                len(b.sharding.device_set) == 4 for b in rest[:n]
            )
            assert p.stats.prefetch_reprimes == 1
        finally:
            p.close()

    def test_reprime_recovers_placement_failure(self):
        """A placement that fails (stale mesh mid-resize) surfaces on
        next(), and reprime with a good placement retries the SAME
        batch instead of dropping it."""

        def broken(b):
            raise ValueError("stale mesh")

        p = DevicePrefetcher(_batches(2), placement=broken, depth=1)
        try:
            with pytest.raises(ValueError, match="stale mesh"):
                next(p)
            p.reprime(lambda b: jax.device_put(b))
            assert int(np.asarray(next(p))[0]) == 0
        finally:
            p.close()

    def test_close_unblocks(self):
        def slow():
            yield np.zeros(2)
            time.sleep(30)
            yield np.ones(2)

        p = DevicePrefetcher(slow(), depth=1)
        next(p)
        p.close()  # must not hang on the sleeping producer
        with pytest.raises(RuntimeError):
            next(p)

    def test_sharded_placement_matches_shard_batch(self):
        from dlrover_tpu.models.train import shard_batch
        from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh

        mesh = build_mesh(MeshConfig(dp=8))
        place = sharded_placement(mesh)
        batch = {"x": np.arange(16, dtype=np.int32).reshape(8, 2)}
        ref = shard_batch(batch, mesh)
        p = DevicePrefetcher(iter([batch]), placement=place)
        try:
            got = next(p)
            assert got["x"].sharding == ref["x"].sharding
            np.testing.assert_array_equal(
                np.asarray(got["x"]), np.asarray(ref["x"])
            )
        finally:
            p.close()

    def test_stats_shared_record(self):
        stats = PipelineStats()
        p = DevicePrefetcher(_batches(5), stats=stats, depth=2)
        try:
            list(p)
            assert stats.prefetch_hits + stats.prefetch_misses == 5
            assert stats.prefetch_overlap_pct is not None
            d = stats.as_dict()
            assert "prefetch_overlap_pct" in d
            assert "stage_backlog_bytes" in d
            assert "donated_bytes" in d
            assert isinstance(stats.summary(), str)
        finally:
            p.close()


class TestTrainerPipeline:
    @pytest.mark.slow  # ~16s: full-pipeline trainer run; budget-gated out
    def test_trainer_prefetch_rewind_and_donation(self, tmp_path):
        """ElasticTrainer with the full pipeline on: prefetched input,
        donation-aware stepping, chunked staging. The run must complete,
        donate on staging-free steps, commit the chunked save, and
        resume from it."""
        import optax

        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
        from dlrover_tpu.models import tiny
        from dlrover_tpu.parallel.mesh import MeshConfig
        from dlrover_tpu.trainer.elastic.trainer import (
            ElasticTrainer,
            TrainerConfig,
        )

        class _Tokens:
            def __init__(self, n=64, seq=32, vocab=256):
                rng = np.random.default_rng(0)
                self.data = rng.integers(
                    0, vocab, (n, seq + 1), dtype=np.int32
                )

            def __len__(self):
                return len(self.data)

            def __getitem__(self, i):
                return {"x": self.data[i][:-1], "y": self.data[i][1:]}

        AsyncCheckpointSaver.reset()
        AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
        try:
            def mk():
                return ElasticTrainer(
                    model_cfg=tiny(),
                    tx=optax.adamw(1e-2),
                    dataset=_Tokens(),
                    trainer_cfg=TrainerConfig(
                        batch_size=8,
                        seq_len=32,
                        ckpt_dir=str(tmp_path / "ckpt"),
                        save_memory_interval=3,
                        save_storage_interval=100,
                        report_metrics=False,
                        log_interval=100,
                        stage_chunk_mb=1,
                    ),
                    strategy=Strategy(
                        mesh=MeshConfig(dp=8), dtype="float32"
                    ),
                )

            t = mk()
            assert t._programs.donating_step is not None
            t.train(num_steps=7)
            assert t.global_step == 7
            s = t.pipeline_stats
            assert s.donated_steps > 0
            assert s.safe_steps > 0  # staging windows ran undonated
            assert s.stage_commits >= 1
            assert s.prefetch_hits + s.prefetch_misses > 0
            # the committed chunked save restores in a fresh trainer
            deadline = time.time() + 60
            while (
                t._ckptr.engine.latest_step(str(tmp_path / "ckpt")) < 3
                and time.time() < deadline
            ):
                time.sleep(0.1)
            # rewind accounting on the SAME trainer (one compile):
            # mid-epoch, and across an epoch rollover with tail batches
            # still buffered — clamping there would skip them on restore
            class _StubPrefetcher:
                def buffered_batches(self):
                    return 2

                def close(self):
                    pass

            t._prefetcher = _StubPrefetcher()
            total = t.sampler._epoch_total()
            t.sampler.epoch, t.sampler.completed_num = 0, 40
            samp = t._ckpt_state()["sampler"]
            assert (samp["epoch"], samp["completed_num"]) == (0, 24)
            t.sampler.epoch, t.sampler.completed_num = 1, 0
            samp = t._ckpt_state()["sampler"]
            assert (samp["epoch"], samp["completed_num"]) == (
                0,
                total - 16,
            )
            # the snapshot never touches the live sampler
            assert (t.sampler.epoch, t.sampler.completed_num) == (1, 0)
            t._prefetcher = None
            t.close()
            t2 = mk()
            assert t2.global_step >= 3
            t2.close()
        finally:
            AsyncCheckpointSaver.reset()
