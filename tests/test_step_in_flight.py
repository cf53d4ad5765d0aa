"""One step in flight (ISSUE 26).

``ElasticTrainer._train_loop`` dispatches step N+1 and only then waits for
step N, so everything else an iteration does (staging a chunk of a flash
save, hooks, the report, beginning a save, the next batch) runs while the
device computes. What must not change: the programs, their order and their
inputs (the losses are bitwise what a plain loop gives), the hook's contract
(once per step, in order, ``trainer.state`` that step's), what a chunked
save commits (the state of the step it began at) and how fast it drains
(one write group on every chunk step), what the report at log cadence says
(that step's loss and learning rate), and that no exit leaves a span open.

A CPU step may well finish before the loop looks, so nothing here asserts
on timing: ``steps_ahead`` is held against a token whose readiness the test
controls.
"""

import itertools
import threading
from types import SimpleNamespace

import jax
import numpy as np
import optax
import pytest

from dlrover_tpu.accel.profiler import PipelineStats
from dlrover_tpu.accel.strategy import Strategy
from dlrover_tpu.ckpt.saver import AsyncCheckpointSaver
from dlrover_tpu.models import tiny
from dlrover_tpu.obs.trace import get_tracer
from dlrover_tpu.parallel.mesh import MeshConfig
from dlrover_tpu.trainer.elastic.trainer import (
    ElasticTrainer,
    TrainerConfig,
    build_optimizer,
)

STEPS = 12
LOG_EVERY = 4
# wide enough that the state (params and both AdamW moments, ~7 MiB) is
# several 1 MiB write groups
MODEL = dict(model_dim=128, mlp_dim=512)


@pytest.fixture
def saver():
    AsyncCheckpointSaver.reset()
    s = AsyncCheckpointSaver.start_async_saving_ckpt(local_shard_num=1)
    yield s
    AsyncCheckpointSaver.reset()


@pytest.fixture
def tracer():
    t = get_tracer()
    t.reset()
    yield t
    t.reset()


class _Tokens:
    def __init__(self, n=512, seq=64, vocab=256, seed=0):
        rng = np.random.default_rng(seed)
        self.data = rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        return {"x": self.data[i][:-1], "y": self.data[i][1:]}


def _trainer(hook=None, tx=None, **cfg):
    return ElasticTrainer(
        tiny(**MODEL),
        tx or optax.adamw(1e-3),
        _Tokens(),
        TrainerConfig(
            batch_size=8, seq_len=64, report_metrics=False,
            log_interval=LOG_EVERY, **cfg,
        ),
        strategy=Strategy(mesh=MeshConfig()),
        devices=jax.devices()[:1],
        metrics_hook=hook,
    )


def _save_cfg(tmp_path, every):
    """A chunked flash save due every ``every`` steps, staged one 1 MiB
    write group a step."""
    return dict(
        ckpt_dir=str(tmp_path / "ckpt"), save_memory_interval=every,
        save_storage_interval=10**9, stage_chunk_mb=1, stage_budget_ms=0.0,
    )


@pytest.fixture(scope="module")
def plain_loop():
    """Losses (as bits) and learning rates of ``STEPS`` steps from calling
    the step function in a plain loop that reads every step: the
    reference, by optimizer."""
    cache = {}

    def run(scheduled: bool):
        if scheduled not in cache:
            t = _trainer(tx=_scheduled() if scheduled else None)
            try:
                state, losses, lrs = t.state, [], []
                for b in itertools.islice(iter(t.dataloader), STEPS):
                    x, y = t._device_batch(b)
                    state, m = t._programs.safe_step(state, x, y)
                    losses.append(np.asarray(m["loss"]).tobytes())
                    hp = getattr(state.opt_state, "hyperparams", None)
                    lrs.append(float(hp["learning_rate"]) if hp else None)
            finally:
                t.close()
            cache[scheduled] = (losses, lrs)
        return cache[scheduled]

    return run


def _scheduled():
    # a learning rate that differs on every step
    return build_optimizer("adamw", lr=1e-3, schedule="linear",
                           total_steps=100)


# -- 1. same programs, same order, same inputs ------------------------------
@pytest.mark.parametrize(
    "case", ["plain", "chunked_save", "hook_reads_every_loss"]
)
def test_losses_are_bitwise_those_of_a_plain_loop(
    case, tmp_path, plain_loop, request
):
    cfg = {}
    if case == "chunked_save":
        request.getfixturevalue("saver")
        cfg = _save_cfg(tmp_path, every=2)
    seen = []

    def hook(step, metrics):
        if case == "hook_reads_every_loss":
            # waits for the step in flight: no overlap, nothing else
            seen.append((step, np.asarray(metrics["loss"]).tobytes()))
        else:
            seen.append((step, metrics["loss"]))

    t = _trainer(hook, **cfg)
    try:
        t.train(num_steps=STEPS)
        stats = t.pipeline_stats
        assert stats.donated_steps + stats.safe_steps == STEPS
        if case == "chunked_save":
            # both twins ran, and the save was committed inside the run
            assert stats.safe_steps > 0 and stats.donated_steps > 0
            assert stats.stage_commits >= 1
    finally:
        t.close()
    assert [s for s, _ in seen] == list(range(1, STEPS + 1))
    got = [
        v if isinstance(v, bytes) else np.asarray(v).tobytes()
        for _, v in seen
    ]
    assert got == plain_loop(False)[0]


# -- 2. the hook's contract -------------------------------------------------
def test_hook_sees_each_step_once_in_order_with_that_steps_state():
    seen, waited = [], []

    def hook(step, metrics):
        seen.append((step, int(t.state.step), metrics["loss"]))

    t = _trainer(hook)
    wait = t._wait_for_step
    t._wait_for_step = lambda done: (waited.append(done), wait(done))[1]
    try:
        t.train(num_steps=STEPS)
    finally:
        t.close()
    assert [(s, at) for s, at, _ in seen] == [
        (n, n) for n in range(1, STEPS + 1)
    ]
    # the wait in step N+1 is for step N: its loss, the very array the
    # hook of step N was handed; the first step has nothing to wait for
    assert waited[0] is None
    assert len(waited) == STEPS
    for done, (_s, _at, loss) in zip(waited[1:], seen):
        assert done is loss


# -- 3. the counter ----------------------------------------------------------
class _Token:
    def __init__(self, ready):
        self.ready, self.blocked = ready, 0

    def is_ready(self):
        return self.ready

    def block_until_ready(self):
        self.blocked += 1
        return self


@pytest.mark.parametrize(
    "ready,counted", [(False, 1), (True, 0)],
    ids=["still_running", "already_done"],
)
def test_steps_ahead_counts_a_step_still_running(ready, counted):
    loop = SimpleNamespace(pipeline_stats=PipelineStats())
    token = _Token(ready)
    ElasticTrainer._wait_for_step(loop, token)
    assert loop.pipeline_stats.steps_ahead == counted
    assert token.blocked == 1  # waited for either way
    ElasticTrainer._wait_for_step(loop, None)  # the first step of a run
    assert loop.pipeline_stats.steps_ahead == counted
    assert "steps_ahead" in loop.pipeline_stats.as_dict()


# -- 4. a chunked save under the lagged loop ----------------------------------
def _leaf_bytes(tree):
    return [np.asarray(x).tobytes() for x in jax.tree_util.tree_leaves(tree)]


def test_chunked_save_commits_its_steps_state_and_drains_every_step(
    saver, tmp_path
):
    # one save in the run: due at step 16, drained long before step 32
    save_at, steps = 16, 30
    rows, at_save = [], {}

    def hook(step, metrics):
        stats = t.pipeline_stats
        rows.append((step, stats.stage_chunks, stats.stage_commits))
        if step == save_at:
            # what the save that begins after this hook must stage
            at_save["train"] = _leaf_bytes(t.state)

    t = _trainer(hook, **_save_cfg(tmp_path, every=save_at))
    try:
        t.train(num_steps=steps)
        by_step = {r[0]: r for r in rows}
        commit = next(s for s, _c, commits in rows if commits == 1)
        # later steps ran while the save drained, and after it
        assert save_at + 3 < commit < steps
        groups = by_step[commit][1]
        assert groups >= 5
        # the drain is not halved: once the write groups are primed (two
        # issued ahead of the one consumed), every step's hook sees one
        # more group staged than the hook before
        for s in range(save_at + 3, commit):
            assert by_step[s][1] - by_step[s - 1][1] >= 1, (s, rows)
        assert commit - save_at <= groups + 3, rows
        # what was committed is the state of the step the save began at,
        # not of a later one: bytes as restored from agent shm
        step, restored = t._ckptr.load_checkpoint(t._ckpt_state())
        assert step == save_at
        assert _leaf_bytes(restored["train"]) == at_save["train"]
    finally:
        t.close()


def test_whole_records_are_staged_without_a_device_computation(
    saver, tmp_path
):
    """The runtime lets 32 computations be in flight: a write group of 40
    small leaves must not queue 40 eager ops behind the step in flight (the
    32nd would block the host until that step ends). A whole record's source
    is the leaf itself, copied to the host as it is, whatever its shape; a
    budgeted advance() leaves the groups it has just issued, however small."""
    from dlrover_tpu.ckpt.engine import CheckpointEngine

    rng = np.random.default_rng(7)
    want = {
        f"leaf{i:02d}": rng.standard_normal(
            [(3, 5), (7,), (), (2, 3, 4)][i % 4]
        ).astype(np.float32)
        for i in range(40)
    }
    state = {k: jax.numpy.asarray(v) for k, v in want.items()}
    engine = CheckpointEngine()
    try:
        stager = engine.begin_chunked_save(
            1, state, str(tmp_path / "ck"), chunk_bytes=1 << 20
        )
        leaves = {id(src) for _rec, src in stager._plan}
        assert stager.advance(budget_s=10.0) == 0  # issued, left to land
        (group,) = stager._inflight
        assert len(group) == 40
        assert all(id(m[3]) in leaves for m in group)
        assert stager.advance(budget_s=10.0) > 0 and stager.done
        assert stager.commit()
        _step, recs, _extra = engine._shm.load_records(copy=True)
        got = {r.path: r.data for r in recs}
        for name, arr in want.items():
            (path,) = [p for p in got if name in p]
            assert got[path].shape == arr.shape
            assert got[path].tobytes() == arr.tobytes()
    finally:
        engine.close()


# -- 5. no exit leaves a span open -------------------------------------------
class _Boom(RuntimeError):
    pass


@pytest.mark.parametrize("where", ["step_function", "hook"])
def test_an_exception_leaves_no_span_open(where, tracer):
    def hook(step, metrics):
        if where == "hook" and step == 5:
            raise _Boom("hook")

    t = _trainer(hook)
    if where == "step_function":
        calls = itertools.count(1)
        real = t._programs.donating_step

        def failing(state, x, y):
            if next(calls) == 5:
                raise _Boom("step")
            return real(state, x, y)

        t._programs.donating_step = failing
    try:
        with pytest.raises(_Boom):
            t.train(num_steps=STEPS)
        assert tracer.open_spans(threading.get_ident()) == []
        done = [r for r in tracer.drain(0)[0] if r[0] == "step"]
        # four whole steps; the fifth was cancelled, not ended
        assert len(done) == 4
    finally:
        t.close()


# -- 6. the report at log cadence ---------------------------------------------
@pytest.mark.parametrize("steps", [STEPS, STEPS - 2],
                         ids=["ends_on_a_report_step", "ends_between"])
def test_report_at_log_cadence_is_that_steps(steps, plain_loop):
    reported = []
    t = _trainer(tx=_scheduled())
    t._report_metrics = lambda step, scalars: reported.append(
        (step, dict(scalars))
    )
    try:
        t.train(num_steps=steps)
    finally:
        t.close()
    losses, lrs = plain_loop(True)
    due = [s for s in range(1, steps + 1) if s % LOG_EVERY == 0]
    assert [s for s, _ in reported] == due
    for step, scalars in reported:
        want = float(np.frombuffer(losses[step - 1], np.float32)[0])
        assert scalars["loss"] == want
        # read from a copy the next (donating) step could not take away
        assert scalars["lr"] == lrs[step - 1]
    assert len({sc["lr"] for _, sc in reported}) == len(reported)
