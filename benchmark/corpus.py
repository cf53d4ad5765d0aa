"""The one traffic generator: batches of token ids from ``--seed``.

A training job's traffic is its batches. Every cell draws them the same
way (the smoke's Zipf-like corpus: something to learn, so the loss falls
within a few steps; uniform tokens would pin it at ln(vocab)); what a cell
varies (batch, sequence length, how often a save falls due, whether the
worker is killed) is data in its ``cells/<name>.json``. Every seed gives
the same shapes in the same number, so a seed changes the tokens and never
the work.
"""

import numpy as np


class Corpus:
    """``rows`` sequences of ``seq + 1`` token ids, made at once."""

    def __init__(self, rows: int, seq: int, vocab: int, seed: int):
        rng = np.random.default_rng(seed)
        p = 1.0 / np.arange(1, vocab + 1)
        cdf = np.cumsum(p / p.sum())
        u = rng.random((rows, seq + 1), dtype=np.float32)
        self.data = np.minimum(
            np.searchsorted(cdf.astype(np.float32), u), vocab - 1
        ).astype(np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        row = self.data[i]
        return {"x": row[:-1], "y": row[1:]}
