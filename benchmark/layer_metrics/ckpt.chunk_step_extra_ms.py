"""Milliseconds a step that stages a chunk of a flash save costs the
host beyond one that stages none: median of (``step`` less the
``device_wait`` inside it) over the steps that hold a ``ckpt_stage``
span, less the same over the steps that hold none (``SpanTracer``, host
clock; the median keeps the steps whose ``ckpt_save`` waits on the
agent's busy saver, up to a second, out of the plain steps' side). The device's own time per step is the same in both, so this is
what staging adds to the critical path; the ``ckpt.stage_*`` readers
say where. A program without ``device_wait`` spans gives nothing."""

LAYER = "flash checkpoint"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return cell["save_memory_interval"] < cell["max_steps"]


def read(run):
    import spans

    return spans.chunk_step_extra_ms(run.spans)
