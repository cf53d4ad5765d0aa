"""Milliseconds a step that stages a chunk spends on the host's own
pass over the chunk's bytes: the stager's ``stage_crc`` (the running
crc32) and ``stage_shm_copy`` (``shm.write_chunk``, the memcpy into the
agent's segment) spans, summed per step that holds a ``ckpt_stage``
span, mean over those steps (``SpanTracer``, host clock). A program
without the spans gives nothing."""

LAYER = "flash checkpoint"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return cell["save_memory_interval"] < cell["max_steps"]


def read(run):
    import spans

    return spans.mean_ms_per_chunk_step(
        run.spans, ("stage_crc", "stage_shm_copy")
    )
