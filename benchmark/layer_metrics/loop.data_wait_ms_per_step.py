"""Milliseconds a step waited for its batch: the ``data_wait`` span, mean
over the window."""

LAYER = "trainer loop"
UNIT = "ms"
MOVES = "tokens_per_s"


def CELLS(cell):
    return True


def read(run):
    spans = [s for s in run.spans if s[0] == "data_wait"]
    if not spans:
        return None
    return sum(s[2] for s in spans) / (1e6 * len(spans))
