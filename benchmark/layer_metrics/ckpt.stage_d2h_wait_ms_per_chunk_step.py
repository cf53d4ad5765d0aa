"""Milliseconds a step that stages a chunk blocks on the chunk's
device-to-host copy: the stager's ``stage_d2h_wait`` spans (the first
touch of a group whose copy was started one group ahead), summed per
step that holds a ``ckpt_stage`` span, mean over those steps
(``SpanTracer``, host clock). A program without the span gives
nothing."""

LAYER = "flash checkpoint"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return cell["save_memory_interval"] < cell["max_steps"]


def read(run):
    import spans

    return spans.mean_ms_per_chunk_step(run.spans, ("stage_d2h_wait",))
