"""Data-driven sequence-parallel scheme selection (review r4 #8).

Parity: the reference hardcodes its scheme per model config
(atorch distributed_transformer/distributed_attention.py — ring-style
DistributedAttention); here the choice reads a MEASURED table.

The table was measured once on a v5e, with the kernel strategy held
constant per row (fused 1024x1024 tiles + online merges vs block-tiled
streaming, both schemes, both strategies timed — r4's
2x "ring wins" verdict turned out to be a kernel-strategy artifact,
not a scheme property). v5e, sp=4, H=16, D=128, bf16, best kernel per
scheme, per-device attention ms:

    seq 4096:  ring 3.83   ulysses 6.29
    seq 8192:  ring 6.91   ulysses 6.86   (a tie)

(A second run measured ring 4.09 / ulysses 4.05 at 4096 —
run-to-run variance swamps sub-10% differences, which is what the tie
margin below exists to absorb.)

Compute converges at long context; what the one-chip table cannot time
is communication, and there the schemes differ structurally: ring's
per-hop ppermute overlaps the next chunk's kernel, while Ulysses pays
two non-overlapped all-to-alls per attention. Ties therefore break to
ring.
"""

from __future__ import annotations

from typing import Dict, Tuple

# (seq -> scheme -> per-device attention ms), measured as described
# above. A row is refreshed, or added, by timing both schemes' attention
# on the chip at that sequence length (sp=4, so a four-chip call; the
# kernels alone: tools/attn_kernel_bench.py) and writing the two numbers
# here with their origin; no benchmark cell runs sp yet (ROADMAP Queue 2
# B8)
MEASURED_MS: Dict[int, Dict[str, float]] = {
    4096: {"ring": 3.83, "ulysses": 6.29},
    8192: {"ring": 6.91, "ulysses": 6.86},
}

# ring's comm overlaps compute, ulysses' all-to-alls do not: a scheme
# must beat ring by this margin on compute before the table flips
_TIE_MARGIN = 0.9


def pick_sp_scheme(seq_len: int) -> str:
    """Scheme for a given global sequence length, from the measured
    table (nearest measured seq — measured at sp=4; other sp degrees
    reuse the nearest row rather than pretending to be keyed on a
    degree that was never measured). Returns ``"ring"`` or
    ``"ulysses"``."""
    if not MEASURED_MS:
        return "ring"
    nearest = min(MEASURED_MS, key=lambda s: abs(s - seq_len))
    row = MEASURED_MS[nearest]
    if row.get("ulysses", 1e9) < row.get("ring", 1e9) * _TIE_MARGIN:
        return "ulysses"
    return "ring"
