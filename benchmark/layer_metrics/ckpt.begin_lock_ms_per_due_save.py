"""Milliseconds the train loop spends asking for the shard lock each time
a flash save falls due: mean of the window's ``ckpt_begin_lock`` spans
(``SpanTracer``, host clock; the engine opens one around the whole
decision of every due save, begun or skipped). A save that falls due
while the agent's saver persists the last one is skipped, and its span
is the time the "no" took: with one step in flight the chip has one
step of work queued, so what a span lasts beyond that is an idle chip.
A program without the span, or a window in which no save fell due,
gives nothing."""

LAYER = "flash checkpoint"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return cell["save_memory_interval"] < cell["max_steps"]


def read(run):
    calls = [s[2] for s in run.spans if s[0] == "ckpt_begin_lock"]
    if not calls:
        return None
    return sum(calls) / (1e6 * len(calls))
