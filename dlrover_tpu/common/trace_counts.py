"""What the modules count while a program is traced.

A module that knows something about the program it is being traced into
(which kernel a call site took, how many serial steps a scan runs) calls
``count(name, n)`` at that place, in Python, while ``jit`` traces: a step
pays nothing, and a program that came whole out of a cache of executables
was not traced and adds nothing. **A counter's name is the
``accel/profiler.PipelineStats`` field it lands in.** The trainer takes a
``snapshot()`` as a train step's build begins and folds ``since(...)`` it
into the stats when it logs the build (``ElasticTrainer._fold_trace_counts``);
it names no counter and no module that counts.

Two scopes, kept as they were when each module held a tally of its own:

- a name in ``RUNNING_TOTALS`` (the attention kernels' eight) is the
  process's running total: the stats hold everything lowered so far, by
  every program, and the log line says what was added since the last line;
- every other name is what was traced since the train step's build began:
  set when a step was built and traced, left as it was when the step came
  out of a cache, and said once.

A new count is one ``count(...)`` where the fact is known and one field on
``PipelineStats`` (``tests/test_trace_counts.py`` holds every name seen to
be such a field).

One fact a module cannot see from its arguments is whether the layer it is
traced into is one the backward pass makes again and that keeps what the
module names for it (``models/transformer.recomputed``): the helper says so
around the trace (``keeping_outputs()``), and a module that counts such
sites asks ``keeping()``.
"""

from __future__ import annotations

import contextlib
import threading
from collections import Counter

RUNNING_TOTALS = (
    "attn_tri_sites",
    "attn_square_sites",
    "attn_tiles_walked",
    "attn_tiles_square",
    "attn_stream_tri_sites",
    "attn_stream_rect_sites",
    "attn_stream_blocks_walked",
    "attn_stream_blocks_rect",
)

# a speculative compile traces on a thread of its own
_lock = threading.Lock()
_counts: Counter = Counter()


class _Keeping(threading.local):
    on = False


_keeping = _Keeping()


@contextlib.contextmanager
def keeping_outputs():
    """Around the trace of a function whose ``jax.checkpoint`` saves what
    the modules named for it (``models/transformer.KEPT``): a call traced
    inside is a site whose named arrays the backward pass reads and does
    not make again (``attn_kept_sites``, ``gdn_kept_sites``). A
    ``custom_vjp``'s forward rule is traced later, when the wrapper is
    differentiated, and outside this."""
    was = _keeping.on
    _keeping.on = True
    try:
        yield
    finally:
        _keeping.on = was


def keeping() -> bool:
    return _keeping.on


def count(name: str, n: int = 1):
    with _lock:
        _counts[name] += int(n)


def snapshot() -> Counter:
    """Every counter as it stands; a name never counted reads 0."""
    with _lock:
        return Counter(_counts)


def since(before: Counter) -> Counter:
    """What was counted after ``before`` was taken, under every name seen
    so far (0 where nothing was added)."""
    now = snapshot()
    return Counter({name: n - before[name] for name, n in now.items()})
