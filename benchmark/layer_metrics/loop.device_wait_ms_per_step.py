"""Milliseconds a step waits for the device: the trainer's
``device_wait`` span (the read of the step count that follows the
dispatch, the loop's one device read per step), mean over the steps of
the window (``SpanTracer``, host clock). Within a few percent of
``step.device_ms`` where the host keeps the device fed; longer where
the launch sat behind something else on the device or the link. A
program without the span gives nothing."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    import spans

    return spans.mean_ms_per_step(run.spans, ("device_wait",))
