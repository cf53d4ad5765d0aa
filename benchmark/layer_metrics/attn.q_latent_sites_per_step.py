"""How many latent-attention sites of the train step project their query
through a latent of its own (``cfg.q_latent_dim`` > 0: down-projection,
RMSNorm, up-projection in place of the whole ``wq``):
``PipelineStats.attn_q_latent_sites`` as the program traced last
(``common/trace_counts``). 4 in the Mistral-Small-4 cell, beside the
device's ``attn.fwd_kernel_runs_per_step`` = 4. A record that the
configured query path is the one that ran; not expected to move. Nothing
to read where the configuration's query is projected whole, or the program
has no such counter."""

import json
import os

LAYER = "step program"
UNIT = "sites"
MOVES = "tokens_per_s"

CONFIGS = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs"
)


def _query_latent(model) -> bool:
    return (model.get("q_latent_dim") or 0) > 0


def CELLS(cell):
    """The cells whose configuration's query passes a latent. A cell of
    another data directory (a rehearsal's) is left to ``read``."""
    try:
        with open(os.path.join(CONFIGS, f"{cell.get('config')}.json")) as f:
            model = json.load(f)["model"]
    except (OSError, ValueError, KeyError, TypeError):
        return True
    return _query_latent(model)


def read(run):
    if not _query_latent(run.config.get("model") or {}):
        return None
    sites = (run.window.get("pipeline") or {}).get("attn_q_latent_sites")
    return float(sites) if sites else None
