"""Share of the window's steps, in percent, at whose ``device_wait`` the
step before was still running: the device had the next step queued behind
it and was never left without work. The rise of
``PipelineStats.steps_ahead`` over the rise of ``donated_steps`` +
``safe_steps`` (every step dispatched is one or the other) between the
window's opening and its close. Its complement is the share of steps
before which the host starved the device. A program without the counter
gives nothing."""

LAYER = "trainer loop"
UNIT = "%"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    opened = run.window.get("pipeline_open") or {}
    closed = run.window.get("pipeline") or {}
    if "steps_ahead" not in opened or "steps_ahead" not in closed:
        return None
    steps = sum(
        closed[k] - opened[k] for k in ("donated_steps", "safe_steps")
    )
    if steps <= 0:
        return None
    return 100.0 * (closed["steps_ahead"] - opened["steps_ahead"]) / steps
