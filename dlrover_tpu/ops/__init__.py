"""TPU kernels + optimizer math.

Replaces the reference's native op layer: ATorch CUDA quantization kernels
(atorch/atorch/ops/csrc/*.cu), flash-attention glue
(modules/transformer/layers.py:54-1168), and the AGD/WSAM optimizers
(optimizers/agd.py:18, wsam.py:11) — as Pallas kernels and optax
transforms.
"""

from dlrover_tpu.ops.flash_attention import flash_attention  # noqa: F401
from dlrover_tpu.ops.int8_matmul import (  # noqa: F401
    int8_einsum_btd_df,
    int8_matmul,
    quantize_int8,
)
from dlrover_tpu.ops.optimizers import agd, make_wsam_grad_fn  # noqa: F401
from dlrover_tpu.ops.quantized_optim import (  # noqa: F401
    adamw_4bit,
    adamw_8bit,
    dequantize_4bit,
    dequantize_8bit,
    quantize_4bit,
    quantize_8bit,
)
