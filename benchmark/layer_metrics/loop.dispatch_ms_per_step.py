"""Milliseconds a step spends in the call of the step function, to its
return: the trainer's ``dispatch`` span (inside ``compute``), mean over
the steps of the window (``SpanTracer``, host clock): argument handling
on the host and the launch queued on the device. The wait for the
device is ``loop.device_wait_ms_per_step``. A program without the span
gives nothing."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    import spans

    return spans.mean_ms_per_step(run.spans, ("dispatch",))
