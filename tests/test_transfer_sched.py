"""Multi-path host-link transfer scheduling (ISSUE 14 tentpole b):
priority arbitration, cooperative preemption, compute-window gating,
aging-bounded starvation, shutdown safety, and the aggregate host-leg
pricing the dry-runner consumes."""

import threading
import time

import pytest

from dlrover_tpu.parallel.transfer_sched import (
    HOST_HIDDEN_FRACTION,
    Priority,
    TransferArbiter,
    aggregate_host_exposed_s,
    get_arbiter,
    set_arbiter,
)


@pytest.fixture(autouse=True)
def _isolated_calibration(monkeypatch, tmp_path):
    """Pricing must not depend on whatever arbiter calibration an
    earlier test (or another run on this machine) left in the real
    topology cache: point the cache at a fresh dir and drop any
    in-process calibration for every test in this file."""
    from dlrover_tpu.parallel import transfer_sched

    monkeypatch.setenv(
        "DLROVER_TPU_TOPOLOGY_CACHE", str(tmp_path / "topo-cache")
    )
    transfer_sched.reset_calibration()
    yield
    transfer_sched.reset_calibration()


@pytest.fixture
def arb():
    a = TransferArbiter(aging_s=0.2, enabled=True)
    yield a
    a.shutdown()


def _hold(arb, stream, nbytes, hold_s, order, tag, priority=None):
    """Worker helper: acquire, note order, hold, release."""
    g = stream.acquire(nbytes, priority=priority)
    order.append(("granted", tag))
    time.sleep(hold_s)
    g.release()
    order.append(("released", tag))
    return g


class TestArbitration:
    def test_uncontended_acquire_is_immediate(self, arb):
        st = arb.register("a")
        t0 = time.perf_counter()
        with st.transfer(1024):
            pass
        assert time.perf_counter() - t0 < 0.05
        assert st.grants == 1
        assert st.bytes_total == 1024

    def test_priority_order_under_contention(self, arb):
        """With the link held, an EMERGENCY waiter is granted before a
        BACKGROUND waiter that enqueued FIRST."""
        holder = arb.register("holder", Priority.BACKGROUND)
        bg = arb.register("bg", Priority.BACKGROUND)
        em = arb.register("em", Priority.EMERGENCY)
        order = []
        g = holder.acquire(1)
        t_bg = threading.Thread(
            target=_hold, args=(arb, bg, 1, 0.0, order, "bg")
        )
        t_bg.start()
        time.sleep(0.05)  # bg is waiting first
        t_em = threading.Thread(
            target=_hold, args=(arb, em, 1, 0.0, order, "em")
        )
        t_em.start()
        time.sleep(0.05)
        g.release()
        t_em.join(timeout=2)
        t_bg.join(timeout=2)
        granted = [t for k, t in order if k == "granted"]
        assert granted == ["em", "bg"]

    def test_emergency_preempts_inflight_spill(self, arb):
        """The satellite corner case: an EMERGENCY checkpoint arrives
        while a spill stream holds the link mid-multi-chunk transfer.
        The holder sees ``should_yield``, releases at its chunk
        boundary, the emergency stream runs to completion, THEN the
        spill resumes."""
        spill = arb.register("emb_spill", Priority.BACKPRESSURE, "d2h")
        ckpt = arb.register("ckpt_emergency", Priority.EMERGENCY, "d2h")
        order = []
        spill_done = threading.Event()

        def spill_worker():
            chunks_left = 20
            while chunks_left:
                g = spill.acquire(1 << 20)
                order.append("spill_granted")
                while chunks_left:
                    time.sleep(0.005)  # one chunk
                    chunks_left -= 1
                    if g.should_yield():
                        order.append("spill_yield")
                        break
                g.release()
            spill_done.set()

        t = threading.Thread(target=spill_worker, daemon=True)
        t.start()
        time.sleep(0.02)  # spill holds, mid-transfer
        with ckpt.transfer(8 << 20):
            order.append("emergency_granted")
            time.sleep(0.02)
        order.append("emergency_done")
        assert spill_done.wait(timeout=5)
        t.join(timeout=2)
        assert "spill_yield" in order
        # emergency completed before the spill's post-yield re-grant
        i_yield = order.index("spill_yield")
        i_done = order.index("emergency_done")
        regrants = [
            i for i, o in enumerate(order)
            if o == "spill_granted" and i > i_yield
        ]
        assert regrants and min(regrants) > i_done
        assert arb.preemptions >= 1

    def test_shutdown_mid_transfer_releases_link(self, arb):
        """Arbiter shutdown while a (wedged) holder owns the link:
        blocked waiters wake with pass-through grants, new acquires
        never block, and the holder's late release is a safe no-op."""
        holder = arb.register("wedged")
        waiter = arb.register("waiter")
        g = holder.acquire(1)  # never released before shutdown
        got = {}

        def blocked():
            got["grant"] = waiter.acquire(1)

        t = threading.Thread(target=blocked, daemon=True)
        t.start()
        time.sleep(0.05)
        assert "grant" not in got  # genuinely blocked
        arb.shutdown()
        t.join(timeout=2)
        assert got["grant"].passthrough
        # new acquires are immediate pass-throughs
        t0 = time.perf_counter()
        with waiter.transfer(1):
            pass
        assert time.perf_counter() - t0 < 0.05
        g.release()  # late release: no-op, no raise

    def test_starvation_bounded_by_aging(self, arb):
        """A BACKGROUND waiter under a constant BACKPRESSURE storm is
        granted within ~(priority gap + 1) x aging_s — the aging knob
        is the starvation bound."""
        storm = arb.register("storm", Priority.BACKPRESSURE)
        bg = arb.register("starved", Priority.BACKGROUND)
        stop = threading.Event()

        def stormer():
            while not stop.is_set():
                with storm.transfer(1):
                    time.sleep(0.01)

        t = threading.Thread(target=stormer, daemon=True)
        t.start()
        time.sleep(0.05)
        t0 = time.perf_counter()
        with bg.transfer(1):
            waited = time.perf_counter() - t0
        stop.set()
        t.join(timeout=2)
        # gap BACKGROUND→BACKPRESSURE is 1 class = aging_s (0.2s);
        # generous bound for a loaded CI box
        assert waited < 1.5

    def test_compute_window_defers_background(self, arb):
        """Outside a fresh compute window BACKGROUND grants wait;
        opening the window releases them. BACKPRESSURE ignores
        windows."""
        arb.note_compute(False)  # marks exist, window closed
        bp = arb.register("bp", Priority.BACKPRESSURE)
        t0 = time.perf_counter()
        with bp.transfer(1):
            pass
        assert time.perf_counter() - t0 < 0.05
        bg = arb.register("bg", Priority.BACKGROUND)
        got = {}

        def bg_acquire():
            g = bg.acquire(1)
            got["t"] = time.perf_counter()
            g.release()

        t = threading.Thread(target=bg_acquire, daemon=True)
        t.start()
        time.sleep(0.08)
        assert "t" not in got  # deferred outside the window
        t_open = time.perf_counter()
        arb.note_compute(True)
        t.join(timeout=2)
        assert got["t"] >= t_open

    def test_ignore_window_exempts_trainer_thread_work(self, arb):
        """Regression (found by the whole-stack e2e drive): the
        ChunkedStager's budgeted advance runs ON the train thread in
        the inter-step section — exactly outside the compute window —
        and must not be deferred by its own gate. ``ignore_window``
        grants pass immediately there; plain BACKGROUND grants still
        defer."""
        arb.note_compute(False)  # gating active, window closed
        st = arb.register("ckpt_stage", Priority.BACKGROUND)
        t0 = time.perf_counter()
        with st.transfer(1 << 20, ignore_window=True):
            pass
        assert time.perf_counter() - t0 < 0.05

    def test_window_marks_expire(self):
        """Stale compute-window marks (trainer gone) stop gating:
        BACKGROUND acquires pass immediately."""
        a = TransferArbiter(aging_s=0.2, enabled=True)
        try:
            a.note_compute(False)
            a._last_mark -= 60.0  # age the mark past WINDOW_TTL_S
            bg = a.register("bg", Priority.BACKGROUND)
            t0 = time.perf_counter()
            with bg.transfer(1):
                pass
            assert time.perf_counter() - t0 < 0.05
        finally:
            a.shutdown()

    def test_disabled_arbiter_is_passthrough(self):
        a = TransferArbiter(enabled=False)
        st = a.register("x")
        g1 = st.acquire(10)
        g2 = st.acquire(10)  # no blocking despite g1 outstanding
        assert g1.passthrough and g2.passthrough
        g1.release()
        g2.release()
        assert st.bytes_total == 20

    def test_forced_grant_on_wedged_holder(self, arb):
        holder = arb.register("wedge")
        waiter = arb.register("w")
        holder.acquire(1)  # wedged: never released
        t0 = time.perf_counter()
        g = waiter.acquire(1, timeout=0.2)
        assert g.passthrough
        assert 0.15 < time.perf_counter() - t0 < 2.0
        assert arb.forced_grants == 1

    def test_env_kill_switch(self, monkeypatch):
        monkeypatch.setenv("DLROVER_TPU_TRANSFER_ARBITER", "0")
        a = TransferArbiter()
        assert not a.enabled


class TestPricing:
    def test_no_demand_prices_zero(self):
        a = TransferArbiter(enabled=True)
        assert aggregate_host_exposed_s(arbiter=a) == 0.0
        a.shutdown()

    def test_scheduled_vs_serialized(self):
        """D2H and H2D are independent physical paths: the scheduled
        estimate exposes the max of the per-direction terms (they
        overlap each other as well as compute), not their sum — the
        sum is the serialized (arbiter-off) world."""
        from dlrover_tpu.parallel.topology import price_host_transfer

        a = TransferArbiter(enabled=True)
        a.set_demand("ckpt_stage", 64 << 20, direction="d2h")
        a.set_demand("emb_fault", 8 << 20, direction="h2d")
        sched = aggregate_host_exposed_s(arbiter=a)
        d2h = price_host_transfer(64 << 20, h2d=False)
        h2d = price_host_transfer(8 << 20, h2d=True)
        # no calibration cache in this test -> documented constant
        assert sched == pytest.approx(
            max(d2h, h2d) * (1.0 - HOST_HIDDEN_FRACTION)
        )
        a.shutdown()  # serialized world: everything exposed, summed
        assert aggregate_host_exposed_s(arbiter=a) == pytest.approx(
            d2h + h2d
        )
        assert sched < d2h + h2d

    def test_measured_calibration_prices_per_rail(self):
        """A calibration cache replaces the constant: pricing uses the
        measured hidden fraction for each direction's rail."""
        from dlrover_tpu.parallel import transfer_sched
        from dlrover_tpu.parallel.topology import price_host_transfer

        cal = transfer_sched.ArbiterCalibration(
            fingerprint=transfer_sched._current_fingerprint(),
            hidden_fraction={"host_d2h": 0.9, "host_h2d": 0.4},
            measured_at=123.0,
            source="test",
        )
        transfer_sched.set_calibration(cal)
        a = TransferArbiter(enabled=True)
        a.set_demand("ckpt_stage", 64 << 20, direction="d2h")
        a.set_demand("emb_fault", 48 << 20, direction="h2d")
        sched = aggregate_host_exposed_s(arbiter=a)
        d2h = price_host_transfer(64 << 20, h2d=False)
        h2d = price_host_transfer(48 << 20, h2d=True)
        assert sched == pytest.approx(
            max(d2h * (1.0 - 0.9), h2d * (1.0 - 0.4))
        )
        a.shutdown()

    def test_dry_runner_est_step_s_sensitivity(self):
        """The acceptance leg: est_step_s must move with the aggregate
        scheduled host bandwidth — registered demand raises the
        estimate by exactly the scheduled host term."""
        import optax

        from dlrover_tpu.accel.dry_runner import compiled_cost
        from dlrover_tpu.accel.strategy import Strategy
        from dlrover_tpu.models import tiny
        from dlrover_tpu.parallel.mesh import MeshConfig

        import jax

        devs = jax.devices()[:1]
        strategy = Strategy(mesh=MeshConfig(dp=1))
        cfg = tiny(num_layers=1)
        tx = optax.sgd(1e-2)
        clean = TransferArbiter(enabled=True)
        set_arbiter(clean)
        try:
            r0 = compiled_cost(strategy, cfg, tx, 2, 16, devs)
            assert r0.ok, r0.error
            assert r0.host_exposed_s == 0.0
            clean.set_demand("ckpt_stage", 256 << 20, direction="d2h")
            r1 = compiled_cost(strategy, cfg, tx, 2, 16, devs)
            assert r1.host_exposed_s > 0.0
            assert r1.est_step_s == pytest.approx(
                r0.est_step_s + r1.host_exposed_s
            )
            # serialized pricing (no scheduling) is strictly worse
            clean.shutdown()
            r2 = compiled_cost(strategy, cfg, tx, 2, 16, devs)
            assert r2.host_exposed_s > r1.host_exposed_s
        finally:
            set_arbiter(None)

    def test_process_arbiter_register_is_get_or_create(self):
        set_arbiter(None)
        a = get_arbiter()
        s1 = a.register("same")
        s2 = a.register("same")
        assert s1 is s2
        assert get_arbiter() is a


class TestStreamIntegration:
    def test_device_tier_streams_registered(self):
        """DeviceSparseEmbedding registers its fault-in (h2d,
        BACKPRESSURE) and spill (d2h) streams, and a training cycle
        moves bytes through them (the arbiter sees the real traffic,
        not a parallel bookkeeping)."""
        import numpy as np

        from dlrover_tpu.ops.embedding import ShardedKvEmbedding
        from dlrover_tpu.ops.embedding.device_tier import (
            DeviceSparseEmbedding,
        )

        fresh = TransferArbiter(enabled=True)
        set_arbiter(fresh)
        try:
            host = ShardedKvEmbedding(2, 8, num_slots=1)
            emb = DeviceSparseEmbedding(
                host,
                capacity=16,
                table_name="arb_t",
                kernel_mode="jnp",
            )
            prep = emb.prepare(np.arange(12, dtype=np.int64))
            emb.release(prep)
            names = {s.name for s in fresh.streams()}
            assert "emb_fault:arb_t" in names
            assert "emb_spill:arb_t" in names
            fault = fresh.register("emb_fault:arb_t")
            assert fault.priority == Priority.BACKPRESSURE
            assert fault.direction == "h2d"
            assert fault.bytes_total > 0  # the fault-in rode a grant
        finally:
            set_arbiter(None)

    def test_sync_spill_under_lock_never_waits_on_link(self):
        """Regression: synchronous (async_spill=False) spills run
        INLINE under the embedding lock — they must not arbitrate,
        or a grant-holding fault-in taking the lock inside
        _host_rows deadlocks ABBA with them. A capacity-thrashing
        sync-spill workload under a held link must finish fast."""
        import numpy as np

        from dlrover_tpu.ops.embedding import ShardedKvEmbedding
        from dlrover_tpu.ops.embedding.device_tier import (
            DeviceSparseEmbedding,
        )

        fresh = TransferArbiter(aging_s=0.2, enabled=True)
        set_arbiter(fresh)
        try:
            host = ShardedKvEmbedding(2, 8, num_slots=1)
            emb = DeviceSparseEmbedding(
                host,
                capacity=8,
                table_name="arb_s",
                kernel_mode="jnp",
                async_spill=False,
            )
            # resident + dirty rows (link still free here)
            ids = np.arange(8, dtype=np.int64)
            prep = emb.prepare(ids)
            emb.release(prep)
            slots = emb.hot.lookup(ids)
            emb.hot._dirty[slots] = True
            # now wedge the link and spill INLINE under the lock —
            # exactly what _allocate does in sync mode. The buggy
            # version arbitrated here and sat behind the holder until
            # the 30s forced-grant backstop.
            blocker = fresh.register("blocker", Priority.EMERGENCY)
            g = blocker.acquire(1)
            t0 = time.perf_counter()
            with emb._lock:
                emb._spill(slots)
            assert time.perf_counter() - t0 < 2.0
            assert fresh.forced_grants == 0
            g.release()
            # the rows landed host-side despite the held link
            assert emb.stats.spill_rows == 8
        finally:
            set_arbiter(None)

    def test_export_metrics_refreshes_demand(self):
        import numpy as np

        from dlrover_tpu.ops.embedding import ShardedKvEmbedding
        from dlrover_tpu.ops.embedding.device_tier import (
            DeviceSparseEmbedding,
        )

        fresh = TransferArbiter(enabled=True)
        set_arbiter(fresh)
        try:
            host = ShardedKvEmbedding(2, 8, num_slots=1)
            emb = DeviceSparseEmbedding(
                host, capacity=16, table_name="arb_d", kernel_mode="jnp"
            )
            prep = emb.prepare(np.arange(10, dtype=np.int64))
            emb.release(prep)
            emb.export_metrics()
            fault = fresh.register("emb_fault:arb_d")
            assert fault.demand_bytes_per_step > 0
            assert aggregate_host_exposed_s(arbiter=fresh) > 0.0
        finally:
            set_arbiter(None)
