"""Minimal elastic GPT-2 training with dlrover-tpu.

Run single-host (from the checkout; `dlrover-tpu-run` once pip-installed):
    python -m dlrover_tpu.trainer.run --nproc-per-node=1 examples/train_gpt2.py

Everything elastic — strategy search, sharding, flash checkpointing,
mid-epoch resume, master-driven batch-size retuning, hang/failure
recovery — lives behind ElasticTrainer.
"""

import numpy as np

from dlrover_tpu.models import gpt2_small
from dlrover_tpu.trainer.elastic.trainer import (
    ElasticTrainer,
    TrainerConfig,
    build_optimizer,
)


class RandomTokens:
    """Stand-in corpus: replace with your tokenized dataset."""

    def __init__(self, n=4096, seq=128, vocab=50257, seed=0):
        self.rng = np.random.default_rng(seed)
        self.data = self.rng.integers(0, vocab, (n, seq + 1), dtype=np.int32)

    def __len__(self):
        return len(self.data)

    def __getitem__(self, i):
        row = self.data[i]
        return {"x": row[:-1], "y": row[1:]}


def main():
    trainer = ElasticTrainer(
        model_cfg=gpt2_small(),
        # warmup + cosine decay, retune-compatible (the master's
        # batch-size linear-scaling factor composes with the schedule)
        tx=build_optimizer(
            "adamw", lr=3e-4, schedule="cosine", warmup_steps=100,
            total_steps=1000, weight_decay=0.01,
        ),
        dataset=RandomTokens(),
        eval_dataset=RandomTokens(n=512, seed=1),
        trainer_cfg=TrainerConfig(
            batch_size=8, seq_len=128, ckpt_dir="/tmp/gpt2_flash_ckpt",
            eval_interval=200, eval_steps=16,
        ),
    )
    trainer.train(num_steps=1000)
    trainer.close()


if __name__ == "__main__":
    main()
