"""Milliseconds of a step that no child span explains: each ``step``
span less its direct children, mean over the steps of the window
(``SpanTracer``, host clock): loop code between the named phases. Read
only where the program partitions the step (it then writes a ``hooks``
span in every step): on a program that does not, the number would be
the phases without a name, not a remainder."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    import spans

    if not any(s[0] == "hooks" for s in run.spans):
        return None
    return spans.unattributed_ms_per_step(run.spans)
