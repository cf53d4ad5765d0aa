"""Milliseconds of a step outside its ``compute`` span: the trainer's
``step`` spans less the ``compute`` child each holds, mean over the steps
that began and ended in the window (``SpanTracer``, host clock). The
``compute`` span covers the dispatch and the wait for the device, so this
is the host work the device cannot hide behind."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    steps = [s for s in run.spans if s[0] == "step"]
    if not steps:
        return None
    tid = steps[0][4]
    computes = [s for s in run.spans if s[0] == "compute" and s[4] == tid]
    outside = 0
    for _n, start, dur, _d, _t in steps:
        inner = sum(
            c[2] for c in computes
            if start <= c[1] and c[1] + c[2] <= start + dur
        )
        outside += dur - inner
    return outside / (1e6 * len(steps))
