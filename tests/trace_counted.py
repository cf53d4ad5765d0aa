"""What the tests read of ``common/trace_counts``: the counts added since
a snapshot, as a tuple in a family's order."""

import importlib

from dlrover_tpu.common import trace_counts

# `dlrover_tpu.ops.flash_attention` the attribute is the function
_fa = importlib.import_module("dlrover_tpu.ops.flash_attention")
FUSED, STREAM, WINDOW = _fa._FUSED, _fa._STREAM, _fa._WINDOW
EDGE = _fa._EDGE
GDN = ("gdn_sites", "gdn_chunk_steps", "gdn_kernel_sites")
PASS = ("gdn_pass_kernel_sites",)
CONV = ("conv_sites", "conv_kernel_sites")
GATE = ("gate_sites", "gate_kernel_sites")
SSD = ("ssd_sites", "ssd_kernel_sites")
LANES = ("attn_score_lanes", "attn_score_lanes_used")
KEPT = ("attn_kept_sites",)
GDN_KEPT = ("gdn_kept_sites",)
SHARE = ("moe_share_kept_sites",)
SSCAN = ("sscan_sites", "sscan_kernel_sites", "sscan_serial_steps")
DIFF = ("attn_diff_pairs", "attn_diff_score_calls")
XDEC = ("xdec_memory_reads", "xdec_kv_reads")
SCALED = (
    "attn_q_latent_sites", "rope_scaled_sites", "attn_pos_scaled_rows",
    "attn_pos_rows",
)
UT = ("ut_steps", "ut_layer_passes", "ut_exit_heads", "ut_exit_fused_heads")


def added(before, names):
    now = trace_counts.since(before)
    return tuple(now[name] for name in names)
