"""Native KvEmbeddingStore: correctness, fused sparse optimizers,
metadata, delta export, and elastic resharding round-trips.

Parity: tfplus kv_variable_test.cc:458 exercises gather/insert/scatter/
import-export against the C++ kernels; here the same contracts are
driven through the ctypes binding.
"""

import os
import threading

import numpy as np
import pytest

from dlrover_tpu.master.elastic_ps import ElasticPsService
from dlrover_tpu.ops.embedding import KvEmbeddingStore, ShardedKvEmbedding


@pytest.fixture(scope="module")
def dim():
    return 8


class TestKvStore:
    def test_gather_or_insert_deterministic(self, dim):
        s1 = KvEmbeddingStore(dim, seed=7)
        s2 = KvEmbeddingStore(dim, seed=7)
        keys = [3, 99, 12345678901]
        np.testing.assert_array_equal(s1.gather(keys), s2.gather(keys))
        # init is per-key deterministic, not ordering-dependent
        np.testing.assert_array_equal(
            s1.gather([99]), s2.gather([1, 99])[1:]
        )
        assert len(s1) == 3
        # different seed → different init
        s3 = KvEmbeddingStore(dim, seed=8)
        assert not np.allclose(s1.gather([3]), s3.gather([3]))

    def test_gather_without_insert_reads_zeros(self, dim):
        s = KvEmbeddingStore(dim)
        out = s.gather([42], insert_missing=False)
        np.testing.assert_array_equal(out, np.zeros((1, dim), np.float32))
        assert len(s) == 0

    def test_scatter_ops(self, dim):
        s = KvEmbeddingStore(dim)
        k = [1, 2]
        ones = np.ones((2, dim), np.float32)
        s.scatter(k, ones * 3, op="update")
        np.testing.assert_array_equal(s.gather(k), ones * 3)
        s.scatter(k, ones, op="add")
        np.testing.assert_array_equal(s.gather(k), ones * 4)
        s.scatter(k, ones * 2, op="mul")
        np.testing.assert_array_equal(s.gather(k), ones * 8)
        s.scatter(k, ones * 5, op="min")
        np.testing.assert_array_equal(s.gather(k), ones * 5)

    def test_sparse_adagrad_matches_numpy(self, dim):
        s = KvEmbeddingStore(dim, num_slots=1, seed=0)
        keys = np.array([10, 20], np.int64)
        w0 = s.gather(keys).copy()
        rng = np.random.default_rng(0)
        acc = np.zeros((2, dim), np.float32)
        w = w0.copy()
        lr, eps = 0.1, 1e-8
        for _ in range(5):
            g = rng.normal(size=(2, dim)).astype(np.float32)
            s.sparse_adagrad(keys, g, lr=lr, eps=eps)
            acc += g * g
            w -= lr * g / (np.sqrt(acc) + eps)
        np.testing.assert_allclose(s.gather(keys), w, rtol=1e-5, atol=1e-6)

    def test_sparse_momentum(self, dim):
        s = KvEmbeddingStore(dim, num_slots=1)
        keys = [5]
        w0 = s.gather(keys).copy()
        g = np.ones((1, dim), np.float32)
        s.sparse_momentum(keys, g, lr=0.1, momentum=0.5)
        s.sparse_momentum(keys, g, lr=0.1, momentum=0.5)
        # m1 = 1, m2 = 1.5 → w = w0 - 0.1*(1 + 1.5)
        np.testing.assert_allclose(
            s.gather(keys), w0 - 0.25, rtol=1e-6, atol=1e-7
        )

    def test_sparse_adam_matches_numpy(self, dim):
        s = KvEmbeddingStore(dim, num_slots=2, seed=0)
        keys = np.array([3, 4], np.int64)
        w = s.gather(keys).copy()
        m = np.zeros((2, dim), np.float32)
        v = np.zeros((2, dim), np.float32)
        lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-8
        rng = np.random.default_rng(1)
        for t in range(1, 6):
            g = rng.normal(size=(2, dim)).astype(np.float32)
            s.sparse_adam(keys, g, lr=lr, step=t, beta1=b1, beta2=b2, eps=eps)
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            mhat = m / (1 - b1**t)
            vhat = v / (1 - b2**t)
            w -= lr * mhat / (np.sqrt(vhat) + eps)
        np.testing.assert_allclose(s.gather(keys), w, rtol=1e-4, atol=1e-6)

    def test_group_ftrl_zeroes_weak_rows(self, dim):
        """The L2,1 penalty must null entire rows with weak signal while
        strong rows survive — the reference's group-sparse behavior.
        (init_scale tiny: the initial weights are seeded into the FTRL
        state, so a large random init is legitimate signal.)"""
        s = KvEmbeddingStore(dim, num_slots=2, seed=0, init_scale=1e-4)
        strong, weak = np.array([1], np.int64), np.array([2], np.int64)
        for _ in range(10):
            s.sparse_group_ftrl(
                strong, np.full((1, dim), 1.0, np.float32),
                alpha=0.5, l21=0.1,
            )
            s.sparse_group_ftrl(
                weak, np.full((1, dim), 1e-3, np.float32),
                alpha=0.5, l21=0.1,
            )
        w_strong = s.gather(strong, insert_missing=False)
        w_weak = s.gather(weak, insert_missing=False)
        assert np.abs(w_strong).sum() > 0
        np.testing.assert_array_equal(w_weak, np.zeros((1, dim)))

    def test_sparse_group_adam_matches_numpy(self, dim):
        """Fused Group Adam vs a step-by-step numpy port of the AGL
        closed-form update (ref training_ops.cc GroupSparseApplyAdamNewV2
        COMPUTE_ADAM macro)."""
        s = KvEmbeddingStore(dim, num_slots=3, seed=0)
        keys = np.array([3, 4], np.int64)
        w = s.gather(keys).copy()
        linear = np.zeros((2, dim), np.float32)
        m = np.zeros((2, dim), np.float32)
        v = np.zeros((2, dim), np.float32)
        lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-8
        l1, l2, l21 = 0.001, 0.01, 0.0001
        rng = np.random.default_rng(2)
        for t in range(1, 6):
            g = rng.normal(size=(2, dim)).astype(np.float32)
            s.sparse_group_adam(
                keys, g, lr=lr, step=t, beta1=b1, beta2=b2, eps=eps,
                l1=l1, l2=l2, l21=l21,
            )
            alpha = np.sqrt(1 - b2**t) / (1 - b1**t)
            m = b1 * m + (1 - b1) * g
            new_v = b2 * v + (1 - b2) * g * g
            sigma_eps = 0.0 if b1 > b1**t else eps
            linear += alpha * m - (
                np.sqrt(new_v) - np.sqrt(v) + sigma_eps
            ) / lr * w
            v = new_v
            u = np.clip(linear, -l1, l1) - linear
            norm = np.sqrt((u * u).sum(axis=1, keepdims=True))
            l21n = l21 * np.sqrt(dim)
            y = (np.sqrt(v) + eps) / lr + 2 * l2
            w = np.where(norm > l21n, u * (1 - l21n / norm) / y, 0.0)
        np.testing.assert_allclose(
            s.gather(keys), w, rtol=1e-4, atol=1e-6
        )

    def test_sparse_group_adam_l21_zeroes_weak_rows(self, dim):
        s = KvEmbeddingStore(dim, num_slots=3, seed=0, init_scale=1e-4)
        strong, weak = np.array([1], np.int64), np.array([2], np.int64)
        for t in range(1, 11):
            s.sparse_group_adam(
                strong, np.full((1, dim), 1.0, np.float32),
                lr=0.05, step=t, l21=0.01,
            )
            s.sparse_group_adam(
                weak, np.full((1, dim), 1e-4, np.float32),
                lr=0.05, step=t, l21=0.01,
            )
        assert np.abs(s.gather(strong, insert_missing=False)).sum() > 0
        np.testing.assert_array_equal(
            s.gather(weak, insert_missing=False), np.zeros((1, dim))
        )

    def test_sparse_lamb_matches_numpy(self, dim):
        s = KvEmbeddingStore(dim, num_slots=2, seed=0)
        keys = np.array([7, 8], np.int64)
        w = s.gather(keys).copy()
        m = np.zeros((2, dim), np.float32)
        v = np.zeros((2, dim), np.float32)
        lr, b1, b2, eps, wd = 0.01, 0.9, 0.999, 1e-6, 0.01
        rng = np.random.default_rng(3)
        for t in range(1, 6):
            g = rng.normal(size=(2, dim)).astype(np.float32)
            s.sparse_lamb(
                keys, g, lr=lr, step=t, beta1=b1, beta2=b2, eps=eps,
                weight_decay=wd,
            )
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            r = m / (1 - b1**t) / (np.sqrt(v / (1 - b2**t)) + eps) + wd * w
            wn = np.sqrt((w * w).sum(axis=1, keepdims=True))
            rn = np.sqrt((r * r).sum(axis=1, keepdims=True))
            ratio = np.where((wn > 0) & (rn > 0), wn / rn, 1.0)
            w -= lr * ratio * r
        np.testing.assert_allclose(
            s.gather(keys), w, rtol=1e-4, atol=1e-6
        )

    def test_sparse_adabelief_matches_numpy(self, dim):
        s = KvEmbeddingStore(dim, num_slots=2, seed=0)
        keys = np.array([11], np.int64)
        w = s.gather(keys).copy()
        m = np.zeros((1, dim), np.float32)
        sv = np.zeros((1, dim), np.float32)
        lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-12
        rng = np.random.default_rng(4)
        for t in range(1, 6):
            g = rng.normal(size=(1, dim)).astype(np.float32)
            s.sparse_adabelief(
                keys, g, lr=lr, step=t, beta1=b1, beta2=b2, eps=eps
            )
            m = b1 * m + (1 - b1) * g
            sv = b2 * sv + (1 - b2) * (g - m) ** 2 + eps
            w -= lr * (m / (1 - b1**t)) / (
                np.sqrt(sv / (1 - b2**t)) + eps
            )
        np.testing.assert_allclose(
            s.gather(keys), w, rtol=1e-4, atol=1e-6
        )

    def test_sparse_amsgrad_matches_numpy(self, dim):
        s = KvEmbeddingStore(dim, num_slots=3, seed=0)
        keys = np.array([13], np.int64)
        w = s.gather(keys).copy()
        m = np.zeros((1, dim), np.float32)
        v = np.zeros((1, dim), np.float32)
        vmax = np.zeros((1, dim), np.float32)
        lr, b1, b2, eps = 0.01, 0.9, 0.999, 1e-8
        rng = np.random.default_rng(5)
        for t in range(1, 6):
            g = rng.normal(size=(1, dim)).astype(np.float32)
            s.sparse_amsgrad(
                keys, g, lr=lr, step=t, beta1=b1, beta2=b2, eps=eps
            )
            m = b1 * m + (1 - b1) * g
            v = b2 * v + (1 - b2) * g * g
            vmax = np.maximum(vmax, v)
            w -= lr * (m / (1 - b1**t)) / (
                np.sqrt(vmax / (1 - b2**t)) + eps
            )
        np.testing.assert_allclose(
            s.gather(keys), w, rtol=1e-4, atol=1e-6
        )

    def test_all_variants_preserve_slots_across_reshard(self, dim):
        """Every fused optimizer's slot state must survive an elastic
        reshard: run one step, reshard 2 -> 3, run a second step, and
        match the same two steps on an unresharded store."""
        variants = [
            ("sparse_adagrad", dict(lr=0.1), 1),
            ("sparse_momentum", dict(lr=0.1), 1),
            ("sparse_adam", dict(lr=0.01, step=1), 2),
            ("sparse_group_adam", dict(lr=0.01, step=1, l1=0.001), 3),
            ("sparse_lamb", dict(lr=0.01, step=1), 2),
            ("sparse_adabelief", dict(lr=0.01, step=1), 2),
            ("sparse_amsgrad", dict(lr=0.01, step=1), 3),
        ]
        rng = np.random.default_rng(6)
        keys = np.arange(32, dtype=np.int64)
        for name, kw, slots in variants:
            g1 = rng.normal(size=(32, dim)).astype(np.float32)
            g2 = rng.normal(size=(32, dim)).astype(np.float32)
            a = ShardedKvEmbedding(2, dim, num_slots=slots, seed=0)
            b = ShardedKvEmbedding(2, dim, num_slots=slots, seed=0)
            for st in (a, b):
                st.gather(keys)
                getattr(st, name)(keys, g1, **kw)
            a.reshard(3)
            kw2 = dict(kw, step=2) if "step" in kw else kw
            for st in (a, b):
                getattr(st, name)(keys, g2, **kw2)
            np.testing.assert_allclose(
                a.gather(keys), b.gather(keys), rtol=1e-5, atol=1e-6,
                err_msg=name,
            )

    def test_freq_and_ts_metadata(self, dim):
        s = KvEmbeddingStore(dim)
        s.gather([7])
        s.gather([7])
        freq, ts = s.meta([7, 8])
        assert freq[0] == 2 and ts[0] > 0
        assert freq[1] == -1 and ts[1] == -1

    def test_eviction_by_timestamp(self, dim):
        s = KvEmbeddingStore(dim)
        s.gather([1, 2, 3])
        assert s.evict_older_than(0) == 0
        evicted = s.evict_older_than(2**62)
        assert evicted == 3 and len(s) == 0

    def test_delta_export(self, dim):
        s = KvEmbeddingStore(dim)
        s.gather([1, 2])
        v = s.version
        s.scatter([2], np.ones((1, dim), np.float32))
        s.gather([3])
        keys, rows, freq, ts = s.export(since_version=v)
        assert sorted(keys.tolist()) == [2, 3]  # only rows touched after v
        keys_full, *_ = s.export()
        assert sorted(keys_full.tolist()) == [1, 2, 3]

    def test_export_import_roundtrip(self, dim):
        a = KvEmbeddingStore(dim, num_slots=1, seed=1)
        keys = np.arange(100, dtype=np.int64)
        a.gather(keys)
        a.sparse_adagrad(keys, np.ones((100, dim), np.float32), lr=0.1)
        b = KvEmbeddingStore(dim, num_slots=1, seed=999)
        b.import_rows(*a.export())
        np.testing.assert_array_equal(
            a.gather(keys, insert_missing=False),
            b.gather(keys, insert_missing=False),
        )
        # slots (adagrad accumulators) travel too: next update identical
        g = np.full((100, dim), 0.5, np.float32)
        a.sparse_adagrad(keys, g, lr=0.1)
        b.sparse_adagrad(keys, g, lr=0.1)
        np.testing.assert_array_equal(a.gather(keys), b.gather(keys))

    def test_concurrent_access(self, dim):
        s = KvEmbeddingStore(dim, num_slots=1)
        errs = []

        def work(tid):
            try:
                rng = np.random.default_rng(tid)
                for _ in range(50):
                    keys = rng.integers(0, 1000, 32)
                    s.gather(keys)
                    s.sparse_adagrad(
                        keys,
                        rng.normal(size=(32, dim)).astype(np.float32),
                        lr=0.01,
                    )
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [
            threading.Thread(target=work, args=(t,)) for t in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        assert 0 < len(s) <= 1000


class TestShardedKvEmbedding:
    def test_routing_consistency(self, dim):
        e = ShardedKvEmbedding(4, dim, seed=3)
        keys = np.arange(500, dtype=np.int64)
        first = e.gather(keys)
        np.testing.assert_array_equal(first, e.gather(keys))
        assert len(e) == 500
        # all shards participate (hash routing spreads keys)
        assert all(len(s) > 0 for s in e.shards)

    def test_reshard_roundtrip_no_loss_no_dup(self, dim):
        """N → M → N with training in between: every row preserved
        exactly once (the review done-criterion)."""
        svc = ElasticPsService()
        e = ShardedKvEmbedding(3, dim, seed=5, version_service=svc)
        keys = np.arange(1000, dtype=np.int64)
        e.gather(keys)
        e.sparse_adagrad(
            keys, np.ones((1000, dim), np.float32), lr=0.05
        )
        before = e.gather(keys, insert_missing=False)
        total_before = len(e)

        e.reshard(5)
        assert svc.get_version("global", "", 0) == 1
        assert len(e) == total_before  # no loss, no duplication
        np.testing.assert_array_equal(
            e.gather(keys, insert_missing=False), before
        )

        e.reshard(2)
        assert len(e) == total_before
        np.testing.assert_array_equal(
            e.gather(keys, insert_missing=False), before
        )
        # optimizer slots survived both reshards: updates stay identical
        ref = ShardedKvEmbedding(1, dim, seed=5)
        ref.import_state(e.export_state())
        g = np.full((1000, dim), 0.3, np.float32)
        e.sparse_adagrad(keys, g, lr=0.05)
        ref.sparse_adagrad(keys, g, lr=0.05)
        np.testing.assert_array_equal(
            e.gather(keys, insert_missing=False),
            ref.gather(keys, insert_missing=False),
        )

    def test_state_checkpoint_roundtrip(self, dim, tmp_path):
        e = ShardedKvEmbedding(2, dim, seed=6)
        keys = np.arange(64, dtype=np.int64)
        e.gather(keys)
        state = e.export_state()
        np.savez(tmp_path / "emb.npz", **state)
        loaded = dict(np.load(tmp_path / "emb.npz"))
        e2 = ShardedKvEmbedding(4, dim, seed=0)
        e2.import_state(loaded)
        np.testing.assert_array_equal(
            e.gather(keys, insert_missing=False),
            e2.gather(keys, insert_missing=False),
        )


class TestSparseTraining:
    def test_embedding_classifier_learns(self, dim):
        """End-to-end sparse training: host-side embedding + fused
        sparse Adagrad + a jax dense head — the TPU recommender shape."""
        import jax
        import jax.numpy as jnp

        emb = ShardedKvEmbedding(2, 16, seed=0)
        rng = np.random.default_rng(0)
        n_ids = 50
        ids = rng.integers(0, n_ids, 512)
        labels = (ids % 2).astype(np.float32)  # parity of the id

        w = jnp.zeros((16,))

        @jax.jit
        def loss_and_grads(w, rows, y):
            logits = rows @ w
            p = jax.nn.sigmoid(logits)
            loss = -jnp.mean(
                y * jnp.log(p + 1e-7) + (1 - y) * jnp.log(1 - p + 1e-7)
            )
            return loss, jax.grad(
                lambda w, r: -jnp.mean(
                    y * jnp.log(jax.nn.sigmoid(r @ w) + 1e-7)
                    + (1 - y)
                    * jnp.log(1 - jax.nn.sigmoid(r @ w) + 1e-7)
                ),
                argnums=(0, 1),
            )(w, rows)

        losses = []
        for epoch in range(30):
            batch_ids = ids[:128]
            y = labels[:128]
            rows = jnp.asarray(emb.gather(batch_ids))
            loss, (gw, grows) = loss_and_grads(w, rows, y)
            losses.append(float(loss))
            w = w - 0.5 * gw
            emb.sparse_adagrad(batch_ids, np.asarray(grows), lr=0.5)
        assert losses[-1] < losses[0] * 0.5, losses[::10]


DIM = 8


class TestWarmReshard:
    """Move-only elastic resharding (ISSUE 12): only rows whose route
    changes leave their shard, values/slots/metadata survive exactly."""

    def _trained(self, shards=4, rows=800, dim=16):
        emb = ShardedKvEmbedding(shards, dim, num_slots=1, seed=3)
        ids = np.arange(rows, dtype=np.int64)
        emb.gather(ids)
        emb.sparse_adagrad(
            ids, np.full((rows, dim), 0.2, np.float32), lr=0.3
        )
        return emb, ids

    def test_values_and_slots_survive_grow_and_shrink(self):
        emb, ids = self._trained()
        rows0, _, _, _ = emb.export_rows(ids)
        rep = emb.warm_reshard(6)
        assert emb.num_shards == 6 and len(emb) == len(ids)
        rows1, _, _, present = emb.export_rows(ids)
        assert present.all()
        np.testing.assert_array_equal(rows0, rows1)
        rep2 = emb.warm_reshard(3)
        assert emb.num_shards == 3 and len(emb) == len(ids)
        rows2, _, _, _ = emb.export_rows(ids)
        np.testing.assert_array_equal(rows0, rows2)
        assert rep.moved_rows > 0 and rep2.moved_rows > 0

    def test_moves_strictly_fewer_rows_than_full(self):
        emb, ids = self._trained()
        rep = emb.warm_reshard(6)
        # the cold path moves EVERY row; warm must move a strict subset
        assert 0 < rep.moved_rows < rep.total_rows
        assert 0.0 < rep.moved_fraction < 1.0

    def test_routing_invariant_after_warm(self):
        """Every row sits in the shard the router says it belongs to —
        a misplaced row would be invisible to routed gathers."""
        emb, ids = self._trained()
        emb.warm_reshard(5)
        route = emb._route(ids)
        for sid, shard in enumerate(emb.shards):
            keys = np.sort(shard.export_keys())
            expect = np.sort(ids[route == sid])
            np.testing.assert_array_equal(keys, expect)

    def test_noop_and_version_bump(self):
        class _V:
            def __init__(self):
                self.v = 0

            def inc_global_version(self):
                self.v += 1

        vs = _V()
        emb = ShardedKvEmbedding(2, DIM, seed=0, version_service=vs)
        emb.gather(np.arange(10))
        rep = emb.warm_reshard(2)
        assert rep.moved_rows == 0 and vs.v == 0  # same count: no-op
        emb.warm_reshard(3)
        assert vs.v == 1

    def test_export_rows_is_a_pure_state_read(self):
        emb = ShardedKvEmbedding(2, DIM, seed=0)
        ids = np.arange(5, dtype=np.int64)
        emb.gather(ids)
        f0, _ = emb.meta(ids)
        emb.export_rows(ids)
        f1, _ = emb.meta(ids)
        np.testing.assert_array_equal(f0, f1)  # no freq bump
        # absent keys are not created
        _, _, _, present = emb.export_rows(np.array([999], np.int64))
        assert not present.any()
        assert len(emb) == 5

    def test_delete_keys(self):
        emb = ShardedKvEmbedding(3, DIM, seed=0)
        ids = np.arange(30, dtype=np.int64)
        emb.gather(ids)
        assert emb.delete_keys(ids[:10]) == 10
        assert emb.delete_keys(ids[:10]) == 0  # already gone
        assert len(emb) == 20


class TestBuildCacheFallback:
    def test_unwritable_cache_dir_falls_back_to_tmpdir(
        self, tmp_path, monkeypatch
    ):
        """An unwritable DLROVER_TPU_KV_CACHE must not crash the import
        path — the build lands in a process-stable tmpdir instead
        (satellite: the PR-6 topology-cache read-only-fs tolerance).
        chmod is useless under root, so the unwritable dir is modeled
        as a cache path occupied by a plain file (same OSError class a
        read-only filesystem raises)."""
        import dlrover_tpu.ops.embedding.store as store_mod

        ro = tmp_path / "not_a_dir"
        ro.write_text("occupied")
        monkeypatch.setenv("DLROVER_TPU_KV_CACHE", str(ro))
        monkeypatch.setattr(store_mod, "_FALLBACK_BUILD_DIR", None)
        path = store_mod._build_library()
        assert os.path.exists(path)
        assert not path.startswith(str(ro))
        # second call reuses the SAME fallback dir (and the cached .so
        # in it — one compile per process, not per call)
        path2 = store_mod._build_library()
        assert path2 == path
