"""One span stream that explains a step and a restore (ISSUE 25).

The trainer partitions its ``step`` span (``data_wait`` / ``compute`` =
``dispatch`` + ``device_wait`` / ``stage`` / ``hooks`` / ``report`` /
``ckpt_save``; since ISSUE 26 ``device_wait`` is the wait for the step
BEFORE the one ``dispatch`` just launched, and the report of a step is
made in the step span after it), the chunked stager names the phases of a
chunk inside
``ckpt_stage``, ``CheckpointEngine.load`` times its phases into
``PipelineStats.restore_*``, every span has a twin on the profiler's
clock (a mirror the process that holds the chip installs), the step
program carries ``scope/<name>`` scopes as metadata only, and the agent
logs a recovery as one timeline.
"""

import importlib.util
import json
import logging
import os
import subprocess
import sys
import threading
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.profiler import (
    RESTORE_FIELDS,
    SAVE_BEGIN_FIELDS,
    STARTUP_FIELDS,
    PipelineStats,
    compile_meter,
)
from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.ckpt.engine import CheckpointEngine
from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
from dlrover_tpu.models import build_train_step, init_sharded_state, tiny
from dlrover_tpu.obs.metrics import MetricsRegistry, fold_pipeline_stats
from dlrover_tpu.obs.trace import SpanTracer, get_tracer
from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
from dlrover_tpu.trainer.elastic.trainer import ElasticTrainer, TrainerConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def saver():
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    yield s
    AsyncCheckpointSaver.reset()


@pytest.fixture
def tracer():
    """The process tracer, emptied, with no mirror left behind."""
    t = get_tracer()
    t.reset()
    yield t
    t.set_mirror(None)
    t.reset()


class _Tokens:
    def __init__(self, n=512, seq=64, vocab=256, seed=0):
        rng = np.random.default_rng(seed)
        self.data = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"x": self.data[i][:-1], "y": self.data[i][1:]}


def _records(tracer, tid=None):
    """``(name, start, end, depth, attrs)`` of the recorded spans."""
    return [
        (name, start, start + dur, depth, attrs)
        for name, t, start, dur, depth, attrs, _seq in tracer.drain(0)[0]
        if tid is None or t == tid
    ]


def _inside(child, parent):
    return parent[1] <= child[1] and child[2] <= parent[2]


def _train(tmp_path, steps, **cfg):
    trainer = ElasticTrainer(
        tiny(),
        optax.adamw(1e-3),
        _Tokens(),
        TrainerConfig(
            batch_size=8, seq_len=64, report_metrics=False,
            log_interval=4, **cfg,
        ),
        strategy=Strategy(mesh=MeshConfig()),
        devices=jax.devices()[:1],
    )
    try:
        trainer.train(num_steps=steps)
        return trainer, dict(trainer.pipeline_stats.as_dict())
    finally:
        trainer.close()


# -- 1. the step is partitioned ---------------------------------------------
@pytest.mark.parametrize("saves", [False, True], ids=["plain", "chunked_save"])
def test_step_span_is_partitioned(saves, tmp_path, tracer, request):
    if saves:
        request.getfixturevalue("saver")
        cfg = dict(
            ckpt_dir=str(tmp_path / "ckpt"), save_memory_interval=4,
            save_storage_interval=10**9, stage_chunk_mb=1,
            stage_budget_ms=0.0,
        )
    else:
        cfg = {}
    _trainer, stats = _train(tmp_path, 14, **cfg)
    main = threading.get_ident()
    recs = _records(tracer, tid=main)
    steps = [r for r in recs if r[0] == "step"]
    assert len(steps) == 14
    # the host's own count names each step on the profiler's clock
    assert [s[4]["step_num"] for s in steps] == list(range(1, 15))
    total = covered = 0
    for step in steps:
        kids = [r for r in recs if _inside(r, step) and r[3] == step[3] + 1]
        names = [k[0] for k in kids]
        assert names[:2] == ["data_wait", "compute"], names
        assert "hooks" in names and names[-1] == "ckpt_save"
        compute = kids[1]
        inner = [r for r in recs if r is not compute and _inside(r, compute)]
        by_name = {r[0]: r for r in inner}
        # the wait comes after the launch, and is for the step before
        # the one launched: the first step has none to wait for, so its
        # `device_wait` is empty (the span is there all the same)
        assert {"dispatch", "device_wait"} <= set(by_name)
        assert by_name["dispatch"][2] <= by_name["device_wait"][1]
        total += step[2] - step[1]
        covered += sum(k[2] - k[1] for k in kids)
    assert covered / total >= 0.99, covered / total
    # the report of steps 4, 8 and 12 (log_interval=4) is made once the
    # step has been waited for: inside the step span after it
    reports = [r for r in recs if r[0] == "report"]
    assert [
        next(s[4]["step_num"] for s in steps if _inside(r, s))
        for r in reports
    ] == [5, 9, 13]
    stage = [r for r in recs if r[0] == "stage"]
    if saves:
        assert stats["stage_commits"] >= 1 and stage
        for st in stage:
            assert any(
                r[0] == "ckpt_stage" and _inside(r, st) for r in recs
            )
            assert any(_inside(st, s) for s in steps)
    else:
        assert not stage  # no stager live: no span, not an empty one


def test_build_spans_carry_the_compile_counters(tmp_path, tracer):
    before = len(compile_meter().builds)
    _train(tmp_path, 3)
    builds = {
        r[0]: r[4] for r in _records(tracer) if r[0].startswith("build:")
    }
    assert {"build:strategy", "build:init", "build:step_donating"} <= set(
        builds
    )
    # each first call of a program compiled something, and says so
    for what in ("build:init", "build:step_donating"):
        assert builds[what]["compiles"] >= 1
        assert 0 < builds[what]["compile_s"] <= builds[what]["seconds"]
    rows = compile_meter().builds[before:]
    assert [r["what"] for r in rows][:2] == ["strategy", "init"]


# -- 2. a chunk's phases ----------------------------------------------------
STAGE_PHASES = (
    "stage_d2h_issue", "stage_d2h_wait", "stage_crc", "stage_shm_copy",
    "stage_grant_wait",
)


def test_chunked_save_names_the_phases_of_a_chunk(saver, tmp_path, tracer):
    engine = CheckpointEngine()
    stats = PipelineStats()
    try:
        state = {
            f"w{i}": jnp.full((2 << 20,), float(i), jnp.float32)
            for i in range(6)
        }
        stager = engine.begin_chunked_save(
            1, state, str(tmp_path / "ck"), chunk_bytes=4 << 20
        )
        assert stager is not None
        while not stager.done:
            stager.advance(budget_s=0.0, stats=stats)
        assert stager.commit(stats=stats)
    finally:
        engine.close()
    recs = _records(tracer, tid=threading.get_ident())
    stages = [r for r in recs if r[0] == "ckpt_stage"]
    phases = [r for r in recs if r[0] in STAGE_PHASES]
    assert {r[0] for r in phases} == set(STAGE_PHASES)
    for p in phases:
        assert any(_inside(p, s) for s in stages), p[0]
    assert stats.stage_chunks == sum(
        1 for r in recs if r[0] == "stage_grant_wait"
    )
    named = sum(p[2] - p[1] for p in phases) / 1e9
    assert named <= stats.stage_block_s
    assert named >= 0.95 * stats.stage_block_s, (named, stats.stage_block_s)


@pytest.mark.parametrize("lock", ["held", "free"])
def test_skipped_saves_reach_the_stats_and_the_gauges(
    lock, saver, tmp_path, tracer
):
    """Saves fall due at steps 4, 8 and 12. While somebody holds the shard
    lock (the saver, over a persist) each is skipped, counted, and answered
    by the lock's mirror; with the lock free none is."""
    if lock == "held":
        assert saver._shard_locks[0]._do_acquire(False, "saver")
    trainer, stats = _train(
        tmp_path, 14, ckpt_dir=str(tmp_path / "ckpt"),
        save_memory_interval=4, save_storage_interval=10**9,
        stage_chunk_mb=1, stage_budget_ms=0.0,
    )
    skips = stats["save_skips"]
    if lock == "held":
        assert skips == stats["lock_local_answers"] == 3
    else:
        # the saver's own persist of a save may still skip the next one
        assert stats["lock_local_answers"] <= skips < 3
    assert stats["stage_commits"] == 3 - skips
    recs = _records(tracer, tid=threading.get_ident())
    asked = [r for r in recs if r[0] == "ckpt_begin_lock"]
    saves = [r for r in recs if r[0] == "ckpt_save"]
    assert len(asked) == 3
    assert all(any(_inside(a, s) for s in saves) for a in asked)
    assert stats["begin_lock_s"] == pytest.approx(
        sum(a[2] - a[1] for a in asked) / 1e9, abs=1e-3
    )
    assert ("saves skipped on a busy shard lock"
            in trainer.pipeline_stats.summary()) == bool(skips)
    registry = MetricsRegistry()
    fold_pipeline_stats(trainer.pipeline_stats, registry)
    gauges = registry.scalars()
    for field in SAVE_BEGIN_FIELDS:
        assert gauges["dlrover_pipeline_" + field] == pytest.approx(stats[field])
    assert SAVE_BEGIN_FIELDS == (
        "save_skips", "begin_lock_s", "lock_local_answers"
    )


# -- 3. the mirror ----------------------------------------------------------
class _Twin:
    def __init__(self, log, name, attrs):
        self.log, self.name, self.attrs = log, name, attrs

    def __enter__(self):
        self.log.append(("enter", self.name, threading.get_ident()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, threading.get_ident()))
        return False


def test_mirror_is_entered_and_left_once_per_span():
    log = []
    t = SpanTracer(capacity=64, enabled=True)
    t.set_mirror(lambda name, attrs: _Twin(log, name, attrs))
    with t.span("step", step_num=7):
        with t.span("compute"):
            pass
        dropped = t.span("data_wait")
        dropped.cancel()
        dropped.cancel()  # a second cancel leaves nothing twice
    me = threading.get_ident()
    assert log == [
        ("enter", "step", me), ("enter", "compute", me),
        ("exit", "compute", me), ("enter", "data_wait", me),
        ("exit", "data_wait", me), ("exit", "step", me),
    ]
    assert [r[0] for r in t.drain(0)[0]] == ["compute", "step"]
    # an inner span leaked open: ending the outer one leaves both twins,
    # innermost first, and a late end of the inner one does nothing more
    del log[:]
    outer = t.span("outer")
    inner = t.span("inner")
    outer.end()
    inner.end()
    assert [e[:2] for e in log] == [
        ("enter", "outer"), ("enter", "inner"),
        ("exit", "inner"), ("exit", "outer"),
    ]
    assert t.open_spans() == []


def test_mirror_faults_and_a_disabled_tracer_cost_the_spans_nothing():
    calls = []

    def broken(name, attrs):
        calls.append(name)
        raise RuntimeError("no profiler here")

    t = SpanTracer(capacity=16, enabled=True)
    t.set_mirror(broken)
    with t.span("a"):
        pass
    assert calls == ["a"] and [r[0] for r in t.drain(0)[0]] == ["a"]
    off = SpanTracer(capacity=16, enabled=False)
    off.set_mirror(broken)
    assert off.span("a") is off.span("b")  # the shared no-op
    assert calls == ["a"]
    t.set_mirror(None)
    with t.span("b"):
        pass
    assert calls == ["a"]


def test_trainer_installs_the_profilers_annotations(tmp_path, tracer):
    _train(tmp_path, 2)
    twin = tracer._mirror("step", {"step_num": 3})
    assert isinstance(twin, jax.profiler.StepTraceAnnotation)
    plain = tracer._mirror("dispatch", None)
    assert type(plain) is jax.profiler.TraceAnnotation
    with twin, plain:  # no session live: enters and leaves, records nothing
        pass


def test_tracer_and_agent_import_without_jax():
    code = (
        "import sys\n"
        "import dlrover_tpu.obs.trace\n"
        "import dlrover_tpu.agent.training_agent\n"
        "import dlrover_tpu.agent.monitor\n"
        "bad = [m for m in sys.modules if m == 'jax' or "
        "m.startswith('jax.')]\n"
        "assert not bad, bad[:3]\n"
    )
    env = dict(os.environ, PYTHONPATH=REPO)
    subprocess.run([sys.executable, "-c", code], check=True, env=env, cwd=REPO)


# -- 4. a restore's phases --------------------------------------------------
@pytest.mark.parametrize("source", ["shm", "storage"])
def test_restore_fills_the_pipeline_stats(source, saver, tmp_path, tracer):
    engine = CheckpointEngine()
    try:
        state = {
            "w": jnp.arange(1 << 18, dtype=jnp.float32),
            "b": jnp.ones((4096,), jnp.float32),
            "step": 9,
        }
        d = str(tmp_path / "ck")
        assert engine.begin_chunked_save(9, state, d).commit()
        deadline = time.time() + 60
        while engine.latest_step(d) < 9:
            time.sleep(0.05)
            assert time.time() < deadline
        assert engine.last_restore is None
        t0 = time.perf_counter()
        step, restored = engine.load(
            state, d, prefer_memory=(source == "shm")
        )
        took = time.perf_counter() - t0
    finally:
        engine.close()
    assert step == 9
    np.testing.assert_array_equal(np.asarray(restored["w"]), state["w"])
    rec = engine.last_restore
    assert set(rec) == set(RESTORE_FIELDS)
    assert rec["restore_source"] == (1 if source == "shm" else 2)
    assert rec["restore_bytes"] >= (1 << 20) + 4096 * 4
    seconds = {k: v for k, v in rec.items() if k.endswith("_s")}
    assert all(v >= 0 for v in seconds.values())
    assert 0 < sum(seconds.values()) <= took
    assert rec["restore_h2d_s"] > 0 and rec["restore_storage_verify_s"] > 0
    if source == "shm":
        assert rec["restore_shm_verify_s"] > 0
        assert rec["restore_storage_read_s"] == 0
    else:
        assert rec["restore_storage_read_s"] > 0
        assert rec["restore_shm_verify_s"] == 0
    # each phase is a span of the field's name less its unit
    names = {r[0] for r in _records(tracer)}
    assert {k[:-2] for k, v in seconds.items() if v > 0} <= names
    # the trainer's fold: fields of the record, gauges of the registry
    stats = PipelineStats()
    stats.set_restore(rec)
    assert stats.as_dict()["restore_source"] == rec["restore_source"]
    assert stats.restore_h2d_s == rec["restore_h2d_s"]
    assert "restored" in stats.summary()
    registry = MetricsRegistry()
    fold_pipeline_stats(stats, registry)
    assert "dlrover_pipeline_restore_h2d_s" in registry.scalars()


# -- 5. stable names on the device ------------------------------------------
SCOPES = (
    "scope/embed", "scope/layer/attn", "scope/layer/mlp",
    "scope/final_norm", "scope/lm_head", "scope/xent", "scope/optimizer",
    "scope/grad_norm",
)


@pytest.fixture(scope="module")
def lowered_step():
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    cfg, tx = tiny(), optax.adamw(1e-3)
    state, _ = init_sharded_state(jax.random.PRNGKey(0), cfg, mesh, tx)
    x = jnp.zeros((8, 32), jnp.int32)
    step = build_train_step(cfg, mesh, tx)
    return step, (state, x, x), step.lower(state, x, x)


@pytest.mark.parametrize("scope", SCOPES)
def test_scope_reaches_the_lowered_step(scope, lowered_step):
    text = lowered_step[2].as_text(debug_info=True)
    assert scope in text
    if scope not in ("scope/optimizer", "scope/grad_norm"):
        # both passes carry the name: the forward's scope rides through
        # the differentiation into the backward's operations
        assert f"jvp({scope})" in text
        assert f"transpose(jvp({scope}))" in text


def test_scopes_are_metadata_only(lowered_step, monkeypatch):
    step, args, lowered = lowered_step
    plain = lowered.as_text()
    assert "scope/" not in plain
    # the same program traced with every scope a no-op: the text that
    # decides the compile cache's key does not move
    from jax._src import source_info_util

    cm = source_info_util.ExtendNameStackContextManager
    monkeypatch.setattr(cm, "__enter__", lambda self: None)
    monkeypatch.setattr(cm, "__exit__", lambda self, *exc: None)
    cfg, tx = tiny(), optax.adamw(1e-3)
    mesh = build_mesh(MeshConfig(), devices=jax.devices()[:1])
    bare = build_train_step(cfg, mesh, tx).lower(*args)
    assert "scope/" not in bare.as_text(debug_info=True)
    assert bare.as_text() == plain


# -- 6. the agent's recovery as one timeline --------------------------------
def _restart_once():
    """An agent whose worker fails once: the run's result, what it
    logged, and the environment it gave each worker it started."""
    from dlrover_tpu.agent.master_client import MasterClient
    from dlrover_tpu.agent.training_agent import (
        ElasticTrainingAgent,
        WorkerSpec,
    )
    from dlrover_tpu.common.log import default_logger
    from dlrover_tpu.master.local_master import start_local_master

    lines, envs = [], []

    class _Capture(logging.Handler):
        def emit(self, record):
            lines.append(record.getMessage())

    master = start_local_master(node_num=1)
    for mgr in master.rdzv_managers.values():
        mgr.update_rdzv_params(min_nodes=1, max_nodes=1, waiting_timeout=0)
    handler = _Capture()
    default_logger.addHandler(handler)
    try:
        agent = ElasticTrainingAgent(
            node_rank=0,
            spec=WorkerSpec(
                entrypoint=os.path.join(REPO, "tests", "assets", "fail_once.py"),
                nproc_per_node=1, max_restarts=2, monitor_interval=0.2,
            ),
            client=MasterClient(master.addr, node_id=0),
        )
        agent.set_checkpoint_hook(lambda: time.sleep(0.05))
        worker_env = agent._worker_env

        def recording_env(*args):
            envs.append(worker_env(*args))
            return envs[-1]

        agent._worker_env = recording_env
        result = agent.run()
    finally:
        default_logger.removeHandler(handler)
        master.stop()
    return result, lines, envs


def _timeline(lines):
    found = [m for m in lines if m.startswith("recovery timeline: ")]
    assert len(found) == 1
    return json.loads(found[0].split(": ", 1)[1])


def test_agent_logs_a_recovery_as_one_timeline(tracer):
    from dlrover_tpu.agent.training_agent import WorkerState

    result, lines, _envs = _restart_once()
    assert result.state == WorkerState.SUCCEEDED and result.restarts == 1
    timeline = _timeline(lines)
    legs = [
        "persist_before_restart_s", "stop_workers_s", "shm_lock_reset_s",
        "rendezvous_s", "start_workers_s",
    ]
    assert set(legs) <= set(timeline)
    assert timeline["reason"] == "worker_failure"
    assert 0.15 <= timeline["detect_tick_s"] <= 5.0
    assert timeline["persist_before_restart_s"] >= 0.05
    assert sum(timeline[k] for k in legs) <= timeline["total_s"] + 0.01
    recs = _records(tracer)
    recover = [r for r in recs if r[0] == "recover"]
    assert len(recover) == 1
    assert recover[0][4]["detect_tick_s"] == timeline["detect_tick_s"]
    inside = [r[0] for r in recs if r[0] != "recover" and _inside(r, recover[0])]
    assert inside == [k[:-2] for k in legs]


# -- 6b. start-up and recovery on one timeline (ISSUE 40) ---------------------
HANDED_LEGS = (
    "reason", "detect_tick_s", "persist_before_restart_s", "stop_workers_s",
    "shm_lock_reset_s", "rendezvous_s",
)


def test_agent_hands_its_legs_to_the_worker_it_starts(tracer):
    from dlrover_tpu.common.constants import NodeEnv

    before = time.monotonic()
    _result, lines, envs = _restart_once()
    first, second = (json.loads(e[NodeEnv.SPAWN_TIMELINE]) for e in envs)
    # a first start hands the instant and no legs
    assert set(first) == {"t_spawn"}
    assert before <= first["t_spawn"] <= second["t_spawn"] <= time.monotonic()
    # a restart hands the legs timed by then, as the log line says them
    timeline = _timeline(lines)
    assert {k: second[k] for k in HANDED_LEGS} == {
        k: timeline[k] for k in HANDED_LEGS
    }
    assert "start_workers_s" not in second and "total_s" not in second
    # ... and the instant just before Popen: inside the start_workers leg
    (leg,) = [r for r in _records(tracer) if r[0] == "start_workers"]
    assert leg[1] <= second["t_spawn"] * 1e9 <= leg[2]
    assert len(envs[1][NodeEnv.SPAWN_TIMELINE]) < 512


@pytest.fixture
def fresh_process(monkeypatch):
    """``init_elastic()`` as a new process finds it: not yet run, its
    record empty (and this process's own left as it was afterwards)."""
    from dlrover_tpu.common.constants import NodeEnv
    from dlrover_tpu.trainer.elastic import distributed

    monkeypatch.setattr(distributed, "_initialized", False)
    monkeypatch.setattr(distributed, "_startup", {})
    monkeypatch.delenv(NodeEnv.SPAWN_TIMELINE, raising=False)
    return distributed


@pytest.mark.parametrize("handed", ["restart", "first_start", "by_hand", "garbled"])
def test_init_elastic_times_its_way_up(handed, fresh_process, monkeypatch, tracer):
    from dlrover_tpu.common.constants import NodeEnv

    legs = {
        "reason": "worker_failure", "restart": 0, "detect_tick_s": 3.0,
        "persist_before_restart_s": 8.5, "stop_workers_s": 0.25,
        "shm_lock_reset_s": 0.5, "rendezvous_s": 1.0,
    }
    value = {
        "restart": json.dumps({**legs, "t_spawn": time.monotonic() - 2.0}),
        "first_start": json.dumps({"t_spawn": time.monotonic() - 2.0}),
        "by_hand": None,
        "garbled": "{'t_spawn': yesterday",
    }[handed]
    if value is not None:
        monkeypatch.setenv(NodeEnv.SPAWN_TIMELINE, value)
    fresh_process.init_elastic()
    rec = fresh_process.startup_record()
    assert rec["startup_backend_s"] > 0
    if handed in ("restart", "first_start"):
        assert 2.0 <= rec["startup_import_s"] < 2.0 + 5.0
    else:
        assert "startup_import_s" not in rec
    if handed == "restart":
        assert rec["recover_detect_tick_s"] == 3.0
        assert rec["recover_persist_s"] == 8.5
        assert rec["recover_respawn_s"] == 1.75
    else:
        assert not any(v for k, v in rec.items() if k.startswith("recover_"))
    # a later call returns early and changes nothing
    fresh_process.init_elastic()
    assert fresh_process.startup_record() == rec
    assert [r[0] for r in _records(tracer)].count("backend_up") == 1
    # what a trainer folds in: the fields, zeros where nothing was handed
    stats = PipelineStats()
    stats.set_startup(rec)
    assert set(rec) <= set(STARTUP_FIELDS)
    assert stats.startup_backend_s == rec["startup_backend_s"]
    assert stats.startup_import_s == rec.get("startup_import_s", 0.0)
    assert ("after a restart" in stats.summary()) == (handed == "restart")


def test_trainer_carries_the_way_up_in_its_stats(tmp_path, tracer, monkeypatch):
    from dlrover_tpu.trainer.elastic import distributed

    handed = {
        "startup_import_s": 6.5, "startup_backend_s": 4.25,
        "recover_detect_tick_s": 3.0, "recover_persist_s": 8.5,
        "recover_respawn_s": 1.75,
    }
    monkeypatch.setattr(distributed, "_initialized", True)
    monkeypatch.setattr(distributed, "_startup", dict(handed))
    before = len(compile_meter().builds)
    trainer, stats = _train(tmp_path, 3)
    assert {k: stats[k] for k in handed} == handed
    # the first step's row, and every row up to it
    rows = compile_meter().builds[before:]
    first = [r["what"] for r in rows].index("step_donating")
    upto = rows[: first + 1]
    assert stats["startup_first_step_s"] == rows[first]["seconds"] > 0
    assert stats["startup_cache_misses"] == sum(
        r["cache_misses"] for r in upto
    )
    assert stats["startup_compile_s"] == pytest.approx(
        sum(r["compile_s"] + r["retrieval_s"] for r in upto), abs=1e-4
    )
    assert 0 < stats["startup_compile_s"] <= sum(r["seconds"] for r in upto)
    assert "up after a restart (tick 3.00 s" in trainer.pipeline_stats.summary()
    registry = MetricsRegistry()
    fold_pipeline_stats(trainer.pipeline_stats, registry)
    gauges = registry.scalars()
    for field in STARTUP_FIELDS:
        assert gauges["dlrover_pipeline_" + field] == pytest.approx(stats[field])
    assert len(STARTUP_FIELDS) == 8


def _reader(metric):
    path = os.path.join(REPO, "benchmark", "layer_metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location("reader_under_test", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# a first incarnation's record at the window's close, a second one's final
# report, and what each reader makes of them
_FIRST = {
    "startup_import_s": 6.5, "startup_backend_s": 4.25,
    "startup_first_step_s": 2.5, "startup_compile_s": 1.5,
    "startup_cache_misses": 0, "recover_detect_tick_s": 0.0,
    "recover_persist_s": 0.0, "recover_respawn_s": 0.0,
}
_SECOND = {
    "startup_import_s": 7.0, "startup_backend_s": 5.0,
    "startup_first_step_s": 8.0, "startup_compile_s": 7.5,
    "startup_cache_misses": 1, "recover_detect_tick_s": 3.0,
    "recover_persist_s": 9.0, "recover_respawn_s": 1.5,
}
READERS = {
    # metric: (its reading, whether it belongs to the kill cell alone)
    "startup.import_s": (6.5, False),
    "startup.backend_s": (4.25, False),
    "startup.first_step_s": (2.5, False),
    "startup.cache_misses": (0, False),
    "agent.detect_tick_s": (3.0, True),
    "agent.persist_before_restart_s": (9.0, True),
    "agent.respawn_s": (1.5, True),
    "restart.import_s": (7.0, True),
    "restart.backend_s": (5.0, True),
    "restart.first_step_s": (8.0, True),
    "restart.cache_misses": (1, True),
    # 26.0 from the last hook to `up`, less tick, persist, respawn, imports
    # and backend: 26.0 - 25.5
    "agent.respawn_unattributed_s": (0.5, True),
}


def _made_up_run(first, second, recovery):
    return types.SimpleNamespace(
        window={"pipeline": first, "pipeline_open": {}},
        reports={0: {}, 1: {"stage": "done", "pipeline": second}},
        recovery=recovery,
    )


@pytest.mark.parametrize("metric", sorted(READERS))
def test_reader_finds_its_field_and_nothing_on_the_parent(metric):
    reading, kill_only = READERS[metric]
    mod = _reader(metric)
    recovery = {"detect_respawn_s": 26.0, "recover_s": 50.0}
    got = mod.read(_made_up_run(_FIRST, _SECOND, recovery))
    assert got == pytest.approx(reading) and got is not None
    # the parent's program: its records hold no such key
    parent = {"restore_source": 1, "restore_shm_verify_s": 2.0}
    assert mod.read(_made_up_run(parent, parent, recovery)) is None
    assert mod.read(_made_up_run({}, {}, recovery)) is None
    # a run that did not come back from its kill has no recovery to read;
    # a steady cell has none by design and still came up
    lone = mod.read(_made_up_run(_FIRST, _SECOND, None))
    assert lone is None if kill_only else lone == pytest.approx(reading)
    # which cells it is read in
    assert mod.CELLS({"kill": True}) is True
    assert mod.CELLS({"kill": False}) is (not kill_only)


def test_new_per_layer_entries_match_their_files():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {}
    for w in bench["workloads"]:
        path = os.path.join(REPO, "benchmark", "cells", w["name"] + ".json")
        with open(path) as f:
            cells[w["name"]] = json.load(f)
    entries = {m["name"]: m for m in bench["per_layer"]}
    # the twelve stand together, in no other entry's place
    names = [m["name"] for m in bench["per_layer"]]
    first = min(names.index(metric) for metric in READERS)
    assert set(names[first:first + 12]) == set(READERS)
    for metric in READERS:
        entry, mod = entries[metric], _reader(metric)
        assert (mod.LAYER, mod.UNIT, mod.MOVES) == (
            entry["layer"], entry["unit"], entry["moves"]
        )
        assert entry["source"] == "program_counter"
        assert entry["better"] == "lower" and entry["moves"] == "setup_s"
        taken = [name for name, cell in cells.items() if mod.CELLS(cell)]
        assert taken == entry.get("workloads", list(cells))


def test_begin_lock_reader_takes_the_mean_of_the_due_saves():
    """``ckpt.begin_lock_ms_per_due_save`` (ISSUE 42): every
    ``ckpt_begin_lock`` span of the window, begun or skipped, and nothing
    where the stream holds none."""
    mod = _reader("ckpt.begin_lock_ms_per_due_save")
    spans = [
        ["step", 0, 100_000_000, 0, 1],
        ["ckpt_begin_lock", 10, 1_200_000_000, 2, 1],  # a slow "no"
        ["ckpt_begin_plan", 20, 5_000_000, 2, 1],
        ["ckpt_begin_lock", 30, 1_000_000, 2, 1],
        ["ckpt_begin_lock", 40, 2_000_000, 2, 1],
    ]
    run = types.SimpleNamespace(spans=spans)
    assert mod.read(run) == pytest.approx(401.0)
    assert mod.read(types.SimpleNamespace(spans=spans[:1])) is None
    assert mod.read(types.SimpleNamespace(spans=[])) is None
    assert mod.CELLS({"save_memory_interval": 50, "max_steps": 800}) is True
    assert mod.CELLS({"save_memory_interval": 10**9, "max_steps": 800}) is False
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    (entry,) = [
        m for m in bench["per_layer"]
        if m["name"] == "ckpt.begin_lock_ms_per_due_save"
    ]
    assert entry == {
        "name": "ckpt.begin_lock_ms_per_due_save", "unit": mod.UNIT,
        "better": "lower", "source": "program_span", "layer": mod.LAYER,
        "moves": mod.MOVES, "workloads": ["gpt2-124m.save-kill-resume"],
    }
    for w in bench["workloads"]:
        path = os.path.join(REPO, "benchmark", "cells", w["name"] + ".json")
        with open(path) as f:
            taken = mod.CELLS(json.load(f))
        assert taken == (w["name"] == "gpt2-124m.save-kill-resume")


KERNEL_SHARE_RUNS = {
    "every_site_in_the_kernel": ("GEGE", {"gdn_sites": 3, "gdn_kernel_sites": 3}, 100.0),
    "one_site_of_three_plain": ("GEGE", {"gdn_sites": 3, "gdn_kernel_sites": 2}, 200 / 3),
    "no_site_in_the_kernel": ("GEGE", {"gdn_sites": 3, "gdn_kernel_sites": 0}, 0.0),
    "a_program_without_the_counter": ("GEGE", {"gdn_sites": 3, "gdn_chunk_steps": 768}, None),
    "no_step_was_traced": ("GEGE", {"gdn_sites": 0, "gdn_kernel_sites": 0}, None),
    "a_window_without_the_record": ("GEGE", None, None),
    "a_configuration_without_the_kind": ("MEM*", {"gdn_sites": 0, "gdn_kernel_sites": 0}, None),
    "the_old_blocks": ("", {"gdn_sites": 3, "gdn_kernel_sites": 3}, None),
}


@pytest.mark.parametrize("case", sorted(KERNEL_SHARE_RUNS))
def test_kernel_sites_share_reads_the_two_counters(case):
    """``gdn.kernel_sites_share`` (ISSUE 44): 100 x ``gdn_kernel_sites``
    over ``gdn_sites`` of the window's ``pipeline`` record; nothing where
    the program keeps no such counter (the parent's traced run prints what
    it printed) or the configuration no Gated DeltaNet layer."""
    pattern, pipeline, want = KERNEL_SHARE_RUNS[case]
    mod = _reader("gdn.kernel_sites_share")
    run = types.SimpleNamespace(
        config={"model": {"layer_pattern": pattern}},
        window={} if pipeline is None else {"pipeline": pipeline},
    )
    got = mod.read(run)
    assert got is None if want is None else got == pytest.approx(want)


def test_kernel_sites_share_is_declared_as_its_reader_says():
    mod = _reader("gdn.kernel_sites_share")
    steps = _reader("gdn.serial_chunk_steps")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the cells whose configuration has the gated delta rule: Gated
    # DeltaNet's (PR 43) and, since PR 45, Kimi Delta Attention's, whose
    # vector decay the kernels do not take (the share reads 0 there);
    # since PR 64 Olmo-Hybrid's, at heads of 96 / 192
    cells = [
        "qwen3-next-80b-a3b-d4.steady", "ling-3.0-flash-d7.steady",
        "olmo-hybrid-7b-d4.steady",
    ]
    (entry,) = [
        m for m in bench["per_layer"] if m["name"] == "gdn.kernel_sites_share"
    ]
    assert entry == {
        "name": "gdn.kernel_sites_share", "unit": mod.UNIT,
        "better": "higher", "source": "program_counter",
        "layer": mod.LAYER, "moves": mod.MOVES, "workloads": cells,
    }
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == ("%", "kernels", "tokens_per_s")
    for w in bench["workloads"]:
        path = os.path.join(REPO, "benchmark", "cells", w["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        assert mod.CELLS(cell) == steps.CELLS(cell) == (w["name"] in cells)


# -- 7. the telemetry writer's race -----------------------------------------
def test_two_threads_publish_one_file(tmp_path):
    """The span heartbeat and the loop's report write one file from two
    threads of one process: neither may take the other's temporary file
    from under it (``FileNotFoundError``, 1 run in 47 on the chip)."""
    from dlrover_tpu.agent.monitor import atomic_write_json

    path = str(tmp_path / "runtime_metrics.json")
    errors = []
    go = threading.Event()

    def writer(who):
        go.wait()
        try:
            for i in range(1500):
                atomic_write_json(path, {"who": who, "i": i, "pad": "x" * 512})
        except Exception as e:  # noqa: BLE001
            errors.append(repr(e))

    threads = [threading.Thread(target=writer, args=(w,)) for w in "ab"]
    for t in threads:
        t.start()
    go.set()
    for t in threads:
        t.join()
    assert errors == []
    with open(path) as f:
        assert json.load(f)["i"] == 1499
    assert os.listdir(tmp_path) == ["runtime_metrics.json"]


# the two site counters' shares, one reader each: ``conv.kernel_sites_share``
# (ISSUE 47) and ``gate.kernel_sites_share`` (ISSUE 63) read the pair of
# their prefix alike
SITES_SHARES = {"conv.kernel_sites_share": "conv", "gate.kernel_sites_share": "gate"}
SITES_SHARE_RUNS = {
    "every_site_in_the_kernel": ("MEM*", {"{p}_sites": 4, "{p}_kernel_sites": 4}, 100.0),
    "every_site_of_a_recomputed_step": ("G-GEGE*E", {"{p}_sites": 18, "{p}_kernel_sites": 18}, 100.0),
    "one_site_of_three_plain": ("GEGE", {"{p}_sites": 3, "{p}_kernel_sites": 2}, 200 / 3),
    "no_site_in_the_kernel": ("GEGE", {"{p}_sites": 3, "{p}_kernel_sites": 0}, 0.0),
    "the_mamba_form_left_plain": ("MEME", {"{p}_sites": 8, "{p}_kernel_sites": 0}, 0.0),
    "a_program_without_the_counter": ("GEGE", {"gdn_sites": 3, "gdn_kernel_sites": 3}, None),
    "a_program_with_half_the_counter": ("MEM*", {"{p}_sites": 4}, None),
    "no_step_was_traced": ("MEM*", {"{p}_sites": 0, "{p}_kernel_sites": 0}, None),
    "a_window_without_the_record": ("GEGE", None, None),
    "a_configuration_without_the_kind": ("E*E*", {"{p}_sites": 2, "{p}_kernel_sites": 2}, None),
    "the_old_blocks": ("", {"{p}_sites": 3, "{p}_kernel_sites": 3}, None),
}


@pytest.mark.parametrize("case", sorted(SITES_SHARE_RUNS))
@pytest.mark.parametrize("name", sorted(SITES_SHARES))
def test_a_sites_share_reads_the_two_counters(name, case):
    """100 x ``<prefix>_kernel_sites`` over ``<prefix>_sites`` of the
    window's ``pipeline`` record; nothing where the program keeps no such
    counter (the parent's traced run prints what it printed) or the
    configuration no Mamba-2 or Gated DeltaNet layer."""
    pattern, pipeline, want = SITES_SHARE_RUNS[case]
    if pipeline is not None:
        pipeline = {
            k.format(p=SITES_SHARES[name]): v for k, v in pipeline.items()
        }
    mod = _reader(name)
    run = types.SimpleNamespace(
        config={"model": {"layer_pattern": pattern}},
        window={} if pipeline is None else {"pipeline": pipeline},
    )
    got = mod.read(run)
    assert got is None if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("name", sorted(SITES_SHARES))
def test_a_sites_share_is_declared_as_its_reader_says(name):
    mod = _reader(name)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # the cells whose configuration has a Mamba-2 or a Gated DeltaNet /
    # Kimi Delta Attention layer
    cells = [
        "nemotron3-nano-30b-a3b-d9.steady", "qwen3-next-80b-a3b-d4.steady",
        "ling-3.0-flash-d7.steady", "olmo-hybrid-7b-d4.steady",
    ]
    # the entry by its name: later PRs append theirs behind it
    (entry,) = [e for e in bench["per_layer"] if e["name"] == name]
    assert entry == {
        "name": name, "unit": mod.UNIT,
        "better": "higher", "source": "program_counter",
        "layer": mod.LAYER, "moves": mod.MOVES, "workloads": cells,
    }
    assert (mod.UNIT, mod.LAYER, mod.MOVES) == ("%", "kernels", "tokens_per_s")
    for w in bench["workloads"]:
        path = os.path.join(REPO, "benchmark", "cells", w["name"] + ".json")
        with open(path) as f:
            cell = json.load(f)
        assert mod.CELLS(cell) == (w["name"] in cells)
    assert mod.CELLS({"config": "no-such-configuration"}) is True
