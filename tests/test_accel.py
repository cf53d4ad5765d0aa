"""auto_accelerate strategy search on the 8-device virtual mesh.

Parity: the reference tests auto_accelerate end-to-end against toy
models (atorch tests); the contract here is (a) candidates respect model
divisibility, (b) the memory gate steers the search away from
replicated-param DP when params don't fit, (c) the returned step fn
actually trains.
"""

import jax
import numpy as np
import optax
import pytest

from dlrover_tpu.accel import (
    Strategy,
    auto_accelerate,
    candidate_strategies,
    dry_run,
)
from dlrover_tpu.accel.dry_runner import compiled_cost
from dlrover_tpu.models import tiny
from dlrover_tpu.parallel.mesh import MeshConfig


def test_candidates_respect_divisibility():
    cfg = tiny(num_layers=4)  # 4 heads, 2 kv heads
    cands = candidate_strategies(cfg, 8, batch=16, seq=64)
    assert cands, "no candidates generated"
    for s in cands:
        m = s.mesh
        assert m.num_devices == 8
        assert cfg.num_heads % m.tp == 0 and cfg.kv_heads % m.tp == 0
        assert cfg.num_layers % m.pp == 0
        assert 16 % (m.dp * m.fsdp) == 0
        assert m.sp == 1  # seq=64 is not long-context
        assert m.ep == 1  # dense model
    # the trivial all-dp mesh must be in the pool
    assert any(s.mesh.dp == 8 for s in cands)


def test_candidates_moe_and_deep():
    moe = tiny(num_experts=4)
    assert any(
        s.mesh.ep == 4 for s in candidate_strategies(moe, 8, 16, 64)
    )
    deep = tiny(num_layers=8)
    cands = candidate_strategies(deep, 8, 16, 64)
    pp_cands = [s for s in cands if s.mesh.pp > 1]
    assert pp_cands and all(s.num_microbatches > 1 for s in pp_cands)


def test_strategy_json_roundtrip():
    s = Strategy(
        mesh=MeshConfig(fsdp=4, tp=2, dcn_axes=("dp",)),
        remat=True,
        num_microbatches=4,
    )
    assert Strategy.from_json(s.to_json()) == s


def _param_dominant_cfg():
    """Params (embed-heavy) dwarf activations, so sharding them matters —
    at true tiny() scale the FSDP all-gather temps outweigh the savings
    and ZeRO shows no memory win."""
    return tiny(
        model_dim=512, mlp_dim=2048, num_layers=2, vocab_size=32768,
        num_heads=8, num_kv_heads=4, max_seq_len=32,
    )


@pytest.mark.slow  # ~10s: AOT compile for cost analysis; budget-gated out
def test_compiled_cost_reports_memory():
    cfg = _param_dominant_cfg()
    tx = optax.adamw(1e-3)
    dp8 = compiled_cost(
        Strategy(mesh=MeshConfig(dp=8), dtype="float32"),
        cfg, tx, 8, 32, jax.devices()[:8],
    )
    fsdp8 = compiled_cost(
        Strategy(mesh=MeshConfig(fsdp=8), dtype="float32"),
        cfg, tx, 8, 32, jax.devices()[:8],
    )
    assert dp8.ok and fsdp8.ok
    assert dp8.mem_bytes > 0 and fsdp8.mem_bytes > 0
    # ZeRO-3 shards params+moments 8 ways: per-device memory must drop
    assert fsdp8.mem_bytes < dp8.mem_bytes


def test_cost_estimate_survives_empty_cost_analysis():
    """review r3 weak#3: an empty XLA cost_analysis() (CPU/virtual
    backends) must NOT collapse every candidate's est_step_s to 0 —
    the fallback is the analytic profiler model, with distinct
    estimates per candidate (remat > plain, pipeline bubble > flat)."""
    from dlrover_tpu.accel.dry_runner import (
        DryRunReport,
        _analytic_estimate,
    )

    cfg = tiny(num_layers=4)
    devs = jax.devices()[:8]

    plain = DryRunReport(strategy=Strategy(mesh=MeshConfig(dp=8)), ok=False)
    _analytic_estimate(plain, cfg, 8, 32, devs)
    assert plain.flops_per_device > 0 and plain.bytes_per_device > 0
    assert plain.est_source == "analytic"

    import dataclasses

    remat = DryRunReport(strategy=Strategy(mesh=MeshConfig(dp=8)), ok=False)
    _analytic_estimate(
        remat, dataclasses.replace(cfg, remat=True), 8, 32, devs
    )
    assert remat.flops_per_device > plain.flops_per_device

    pp = DryRunReport(
        strategy=Strategy(
            mesh=MeshConfig(pp=2, dp=4), num_microbatches=4
        ),
        ok=False,
    )
    _analytic_estimate(pp, cfg, 8, 32, devs)
    # same total work but a (pp-1)/M bubble → higher effective cost
    assert pp.flops_per_device > plain.flops_per_device

    # end-to-end: whatever the backend's cost analysis returns, a
    # successful compile must carry a usable non-zero estimate
    rep = compiled_cost(
        Strategy(mesh=MeshConfig(dp=8), dtype="float32"),
        cfg, optax.adamw(1e-3), 8, 32, devs,
    )
    assert rep.ok and rep.est_step_s > 0, (rep.est_source, rep.est_step_s)


def test_cost_estimate_gates_implausible_xla_analysis():
    """review r4 weak#2: a NONEMPTY but bogus cost_analysis() (virtual
    backends returned est 7.4 us for a measured 26 ms step, 3,500x off,
    labeled [xla]) must be caught by the analytic-lower-bound gate and
    fall back to the analytic tier, relabeled."""
    from dlrover_tpu.accel.dry_runner import (
        DryRunReport,
        _analytic_estimate,
        _finalize_estimate,
    )

    cfg = tiny(num_layers=4)
    devs = jax.devices()[:8]
    bound = DryRunReport(strategy=Strategy(mesh=MeshConfig(dp=8)), ok=False)
    _analytic_estimate(bound, cfg, 8, 32, devs)

    # bogus: flops far below the analytic lower bound
    bogus = DryRunReport(strategy=Strategy(mesh=MeshConfig(dp=8)), ok=False)
    bogus.flops_per_device = bound.flops_per_device / 1000.0
    bogus.bytes_per_device = 1.0
    _finalize_estimate(bogus, cfg, 8, 32, devs)
    assert bogus.est_source == "analytic(xla-implausible)"
    assert bogus.est_step_s >= bound.est_step_s * 0.99

    # plausible: flops at/above the bound stay labeled xla
    sane = DryRunReport(strategy=Strategy(mesh=MeshConfig(dp=8)), ok=False)
    sane.flops_per_device = bound.flops_per_device * 1.5
    sane.bytes_per_device = bound.bytes_per_device
    _finalize_estimate(sane, cfg, 8, 32, devs)
    assert sane.est_source == "xla"
    assert sane.est_step_s > 0


def test_sp_auto_reads_measured_table():
    """sp candidates carry the sp_auto optimization; applying it sets
    cfg.sp_scheme from the measured kernel-constant table
    (parallel/sp_select.py) — review r4 #8."""
    import dataclasses

    from dlrover_tpu.accel.opt_lib import apply_optimizations
    from dlrover_tpu.parallel.sp_select import MEASURED_MS, pick_sp_scheme

    cfg = dataclasses.replace(tiny(), max_seq_len=4096)
    s = Strategy(mesh=MeshConfig(sp=4, dp=2), opts=("sp_auto",))
    cfg2, s2 = apply_optimizations(cfg, s, s.opts)
    assert cfg2.sp_scheme == pick_sp_scheme(4096)
    # the table is the source of truth: a fake table must flip the pick
    orig = dict(MEASURED_MS)
    try:
        MEASURED_MS.clear()
        MEASURED_MS[4096] = {"ring": 10.0, "ulysses": 1.0}
        assert pick_sp_scheme(4096) == "ulysses"
        MEASURED_MS[4096] = {"ring": 1.0, "ulysses": 1.05}
        assert pick_sp_scheme(4096) == "ring"  # tie -> comm overlap
    finally:
        MEASURED_MS.clear()
        MEASURED_MS.update(orig)
    # non-sp strategies are untouched
    cfg3, _ = apply_optimizations(
        cfg, Strategy(mesh=MeshConfig(dp=8), opts=("sp_auto",)),
        ("sp_auto",),
    )
    assert cfg3.sp_scheme == cfg.sp_scheme


@pytest.mark.slow
def test_memory_gate_beats_naive_dp():
    """With an HBM budget only a sharded layout satisfies, the search
    must reject replicated-param DP and pick a non-trivial mesh.

    Marked slow: this is a full 16-candidate compile sweep (~60 s on
    one CPU — the single heaviest test in the suite, and capping the
    candidate list just trips the remat-retry search into compiling
    MORE). The search/ranking machinery it drives stays tier-1-covered
    by test_auto_accelerate_search / bayes / optimizations-once; the
    memory-gate-specific assertion runs in the slow tier."""
    cfg = _param_dominant_cfg()
    tx = optax.adamw(1e-3)
    devices = jax.devices()[:8]
    dp8 = compiled_cost(
        Strategy(mesh=MeshConfig(dp=8), dtype="float32"),
        cfg, tx, 8, 32, devices,
    )
    budget = dp8.mem_bytes * 0.6  # naive DP cannot fit this
    result = auto_accelerate(
        cfg, tx, batch=8, seq=32, devices=devices,
        hbm_budget=budget, max_timed=1,
    )
    m = result.strategy.mesh
    assert m.dp < 8, f"expected non-trivial mesh, got {m.axis_sizes()}"
    assert result.reports[0].mem_bytes <= budget
    # and the winner actually trains
    state = result.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    state, metrics = result.step_fn(state, x, x)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.skipif(
    jax.__version_info__ < (0, 5, 0),
    reason="pp+dp partial-manual shard_map needs PartitionId SPMD support",
)
def test_auto_accelerate_with_pinned_strategy():
    cfg = tiny(num_layers=4)
    tx = optax.adamw(1e-3)
    pinned = Strategy(
        mesh=MeshConfig(pp=2, dp=4), dtype="float32", num_microbatches=4
    )
    result = auto_accelerate(
        cfg, tx, batch=8, seq=32, devices=jax.devices()[:8],
        strategy=pinned,
    )
    assert result.strategy == pinned
    state = result.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (8, 32)).astype(np.int32)
    state, metrics = result.step_fn(state, x, x)
    assert np.isfinite(float(metrics["loss"]))


def test_tpe_propose_prefers_good_region():
    """TPE must propose the pool candidate nearest the good observations
    in feature space."""
    from dlrover_tpu.accel.bayes import tpe_propose

    def s(dp, fsdp):
        return Strategy(mesh=MeshConfig(dp=dp, fsdp=fsdp))

    # observed: big-fsdp fast (good), big-dp slow (bad)
    tried = [s(8, 1), s(4, 2), s(1, 8), s(2, 4)]
    scores = [0.9, 0.5, 0.1, 0.12]
    pool = [s(1, 4), s(4, 1)]
    pick = tpe_propose(tried, scores, pool)
    assert pick.mesh.fsdp == 4, pick.describe()


def test_tpe_propose_handles_failures():
    from dlrover_tpu.accel.bayes import tpe_propose

    def s(dp):
        return Strategy(mesh=MeshConfig(dp=dp))

    tried = [s(8), s(4)]
    scores = [None, 0.2]  # first crashed
    pick = tpe_propose(tried, scores, [s(2), s(1)])
    assert pick.mesh.dp in (1, 2)


def test_hbm_gate_tristate_consistent_across_search_paths(monkeypatch):
    """When the backend offers NO memory analysis (mem_bytes == 0), both
    search paths must classify the candidate identically — fits=None
    ("unknown", still viable) — so a job cannot pass under
    search='combination' and fail under search='bayes'."""
    import dlrover_tpu.accel.bayes as bayes_mod
    import dlrover_tpu.accel.dry_runner as dr_mod
    from dlrover_tpu.accel.bayes import tpe_search

    cfg = tiny(num_layers=1)
    tx = optax.adamw(1e-3)
    devices = jax.devices()[:2]
    cands = [Strategy(mesh=MeshConfig(dp=2), dtype="float32")]

    # backend-without-memory-analysis: timed_run measures but mem=0
    real_timed = dr_mod.timed_run

    def no_mem_timed(*a, **k):
        t, _ = real_timed(*a, **k)
        return t, 0.0

    monkeypatch.setattr(bayes_mod, "timed_run", no_mem_timed)
    reports = tpe_search(
        cands, cfg, tx, 2, 16, devices, budget=1, n_init=1,
        timed_steps=1, hbm_budget=1e9,
    )
    best = reports[0]
    assert best.step_s is not None
    assert best.fits is None, "unknown memory must not fail the TPE path"
    # both paths import the ONE shared gate, so the semantic is
    # structurally identical; pin its tri-state contract
    assert bayes_mod.hbm_fits is dr_mod.hbm_fits
    assert dr_mod.hbm_fits(0.0, 1e9) is None
    assert dr_mod.hbm_fits(2e9, 1e9) is False
    assert dr_mod.hbm_fits(5e8, 1e9) is True
    assert dr_mod.hbm_fits(0.0, None) is True  # no budget -> no gate


@pytest.mark.slow  # ~23s end-to-end TPE search + compile; the TPE
# machinery itself (tpe_propose/tpe_search, hbm gating, dry-run
# consistency) stays tier-1 in the unit tests above — budget
def test_auto_accelerate_bayes_search():
    """The TPE path returns a measured, trainable winner."""
    cfg = tiny(num_layers=2)
    tx = optax.adamw(1e-3)
    result = auto_accelerate(
        cfg, tx, batch=16, seq=32, devices=jax.devices(),
        max_candidates=6, max_timed=1, search="bayes",
    )
    assert result.reports[0].step_s is not None
    state = result.init_fn(jax.random.PRNGKey(0))
    from dlrover_tpu.models import shard_batch

    x = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (16, 32)
    ).astype(np.int32)
    if result.strategy.mesh.pp > 1:
        bx = by = x
    else:
        b = shard_batch({"x": x, "y": x}, result.mesh)
        bx, by = b["x"], b["y"]
    state, metrics = result.step_fn(state, bx, by)
    assert np.isfinite(float(metrics["loss"]))


def test_opt_lib_registry_and_apply():
    from dlrover_tpu.accel import apply_optimizations, registered_optimizations
    from dlrover_tpu.accel.opt_lib import register_optimization

    assert {"remat", "bf16", "fp32", "int8_mlp", "1f1b"} <= set(
        registered_optimizations()
    )
    cfg = tiny()
    s = Strategy(mesh=MeshConfig(dp=8))
    cfg2, s2 = apply_optimizations(cfg, s, ["remat", "int8_mlp", "remat"])
    assert s2.remat and cfg2.int8_mlp
    assert s2.opts == ("remat", "int8_mlp")  # deduplicated, ordered

    register_optimization(
        "test_double_mb",
        lambda c, st: (c, st.__class__(**{
            **st.__dict__, "num_microbatches": st.num_microbatches * 2,
        })),
    )
    _, s3 = apply_optimizations(cfg, s, ["test_double_mb"])
    assert s3.num_microbatches == 2

    with pytest.raises(KeyError):
        apply_optimizations(cfg, s, ["not_registered"])


def test_strategy_json_carries_opts():
    """agree_strategy ships strategies as JSON — named opts must round-
    trip so the receiving host rebuilds the identical program."""
    s = Strategy(
        mesh=MeshConfig(pp=2, dp=4),
        num_microbatches=4,
        pp_schedule="1f1b",
        opts=("remat", "int8_mlp"),
    )
    rt = Strategy.from_json(s.to_json())
    assert rt == s
    assert "1f1b" in rt.describe() and "int8_mlp" in rt.describe()


def test_build_rederives_cfg_from_opts():
    """_build must re-apply cfg-level opts recorded on the strategy (the
    other-host path: the strategy arrives as JSON, not the config)."""
    from dlrover_tpu.accel.dry_runner import _build

    cfg = tiny(num_layers=2)
    assert not cfg.int8_mlp
    s = Strategy(mesh=MeshConfig(dp=8), dtype="float32", opts=("int8_mlp",))
    cfg2, mesh, step_fn, init_fn, make_batch, _ = _build(
        s, cfg, optax.adamw(1e-3), jax.devices()
    )
    assert cfg2.int8_mlp
    state = init_fn(jax.random.PRNGKey(0))
    x, y = make_batch(8, 16)
    state, metrics = step_fn(state, x, y)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.skipif(
    jax.__version_info__ < (0, 5, 0),
    reason="pp+dp partial-manual shard_map needs PartitionId SPMD support",
)
def test_pinned_1f1b_strategy_through_driver():
    cfg = tiny(num_layers=2)
    tx = optax.adamw(1e-3)
    s = Strategy(
        mesh=MeshConfig(pp=2, dp=4),
        dtype="float32",
        num_microbatches=4,
        pp_schedule="1f1b",
    )
    result = auto_accelerate(
        cfg, tx, batch=8, seq=16, devices=jax.devices(), strategy=s
    )
    state = result.init_fn(jax.random.PRNGKey(0))
    x = np.random.default_rng(0).integers(
        0, cfg.vocab_size, (8, 16)
    ).astype(np.int32)
    state, metrics = result.step_fn(state, x, x)
    assert np.isfinite(float(metrics["loss"]))


@pytest.mark.slow  # ~24s: repeated recompiles; budget-gated out of tier-1
def test_optimizations_applied_exactly_once():
    """Non-idempotent registered opts must not compound across the
    candidate/search/build stages (names are recorded; _build applies)."""
    from dataclasses import replace as dc_replace

    from dlrover_tpu.accel.opt_lib import register_optimization

    register_optimization(
        "test_add_layers",
        lambda c, s: (dc_replace(c, num_layers=c.num_layers + 2), s),
    )
    cfg = tiny(num_layers=2)
    result = auto_accelerate(
        cfg, optax.adamw(1e-3), batch=8, seq=16, devices=jax.devices(),
        max_candidates=2, max_timed=1,
        optimizations=("test_add_layers",),
    )
    assert result.cfg.num_layers == 4  # once, not 6 or 8
    assert result.strategy.opts == ("test_add_layers",)


def test_pinned_strategy_honors_optimizations():
    cfg = tiny(num_layers=2)
    result = auto_accelerate(
        cfg, optax.adamw(1e-3), batch=8, seq=16, devices=jax.devices(),
        strategy=Strategy(mesh=MeshConfig(dp=8), dtype="float32"),
        optimizations=("int8_mlp",),
    )
    assert result.cfg.int8_mlp
    assert "int8_mlp" in result.strategy.opts


def test_grad_accum_threaded_through_strategy():
    """auto_accelerate(grad_accum=K) stamps K onto the winning strategy
    and the produced step really accumulates (batch splits into K)."""
    cfg = tiny(num_layers=2)
    tx = optax.adamw(1e-3)
    pinned = Strategy(mesh=MeshConfig(dp=8), dtype="float32")
    result = auto_accelerate(
        cfg, tx, batch=16, seq=32, devices=jax.devices()[:8],
        strategy=pinned, grad_accum=2,
    )
    assert result.strategy.grad_accum == 2
    assert "ga2" in result.strategy.describe()
    rt = Strategy.from_json(result.strategy.to_json())
    assert rt.grad_accum == 2
    state = result.init_fn(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    x = rng.integers(0, cfg.vocab_size, (16, 32)).astype(np.int32)
    state, metrics = result.step_fn(state, x, x)
    assert np.isfinite(float(metrics["loss"]))


def test_candidates_include_interleaved_for_deep_models():
    from dlrover_tpu.accel.candidates import candidate_strategies

    cfg = tiny(num_layers=8, num_experts=0)
    cands = candidate_strategies(cfg, 8, 8, 64, max_candidates=32)
    il = [s for s in cands if s.pp_schedule == "interleaved"]
    assert il, "deep model should yield interleaved pp candidates"
    for s in il:
        assert s.mesh.pp > 1
        assert cfg.num_layers % (s.mesh.pp * s.pp_virtual) == 0


def test_grad_accum_rejects_pp_and_bad_batch():
    cfg = tiny(num_layers=4)
    tx = optax.adamw(1e-3)
    with pytest.raises(ValueError, match="num_microbatches"):
        auto_accelerate(
            cfg, tx, batch=8, seq=32, devices=jax.devices()[:8],
            strategy=Strategy(
                mesh=MeshConfig(pp=2, dp=4), num_microbatches=4
            ),
            grad_accum=2,
        )
    with pytest.raises(ValueError, match="divide"):
        auto_accelerate(
            cfg, tx, batch=6, seq=32, devices=jax.devices()[:8],
            grad_accum=4,
        )


def test_candidates_respect_grad_accum_microbatch_divisibility():
    """The unit sharded over dp*fsdp is batch/K: dp=8 must be pruned
    when batch=8 and K=4 (microbatch 2 cannot shard 8 ways), and pp
    candidates never carry grad_accum."""
    from dlrover_tpu.accel.candidates import candidate_strategies

    cfg = tiny(num_layers=8, num_experts=0)
    cands = candidate_strategies(cfg, 8, 8, 64, grad_accum=4)
    for s in cands:
        if s.mesh.pp > 1:
            assert s.grad_accum == 1
        else:
            assert s.grad_accum == 4
            assert (8 // 4) % (s.mesh.dp * s.mesh.fsdp) == 0
    assert all(s.mesh.dp * s.mesh.fsdp <= 2 or s.mesh.pp > 1 for s in cands)
