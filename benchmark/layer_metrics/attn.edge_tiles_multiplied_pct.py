"""Score tiles the edge blocks of the streaming attention kernels' triangle
path multiply, in percent of the tiles those blocks hold
(``PipelineStats.attn_edge_tiles_multiplied`` over ``attn_edge_tiles``: the
trainer sets both from what the train step's build traced,
``ops/flash_attention._count_edge_tiles``, each summed over a head's walk
and the kernels, forward and backward). An edge block is one its rows see
part of: the block on the diagonal, and under a window each block the
window's far edge crosses. A kernel that walks it in row strips multiplies
each strip against the one span of the block's keys that any of its rows
sees (``_edge_strips``); a tile is a strip's height a side, and a tile
wholly over the diagonal or past the window is not multiplied. In four
strips that is 10 of a diagonal block's 16 tiles, the same of the block
Trinity-Mini's window of 2048 crosses in blocks of 1024, and 9 and 3 of 16
of the two blocks of Phi-4-mini-flash's window of 512. The backward
kernels walk strips and the forward kernel computes an edge block whole, so
a forward and a one-pass backward over diagonal blocks alone read 81.25 (26
of 32), and 87.5 (42 of 48) in a configuration that recomputes its layers,
whose forward is traced twice and kept, so run once; 100 says every edge
block was computed whole and masked. Lower is
better: it is what ``kernel.attn_roofline`` and
``kernel.attn_window_roofline`` are paid for on the edges (the blocks
walked, ``attn.window_blocks_walked_pct``, are not moved by it). Nothing to
read where the cell's rows are short enough for the fused family, which
walks tiles of its own (``attn_tiles_walked``), or the program has no such
counter."""

LAYER = "kernels"
UNIT = "%"
MOVES = "tokens_per_s"

# the longest row the fused family takes (``ops/flash_attention
# ._FUSED_MAX_T``): past it every causal call streams
FUSED_MAX_T = 1024


def CELLS(cell):
    """The cells whose rows are longer than the fused family takes."""
    return (cell.get("seq") or 0) > FUSED_MAX_T


def read(run):
    pipeline = run.window.get("pipeline") or {}
    held = pipeline.get("attn_edge_tiles")
    if not held or "attn_edge_tiles_multiplied" not in pipeline:
        return None
    return 100.0 * pipeline["attn_edge_tiles_multiplied"] / held
