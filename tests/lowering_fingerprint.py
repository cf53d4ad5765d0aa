"""What a benchmark configuration's parameter tree and train step look
like to the compiler, as two sha256: the tree's paths, shapes and dtypes,
and the text the step lowers to (its own optimizer, one device, a batch of
1 x 1024). ``tests/data/step_lowering.json`` holds what the commit before
ISSUE 37 gave for the three configurations the benchmark had then, what the
commit before ISSUE 43 gave for the Nemotron configuration, and what ISSUE
44 gave for ``qwen3-next-80b-a3b-d4`` (its tree is ISSUE 43's; its step was
recorded anew when the delta rule's output took the activation dtype inside
``gated_delta_chunked``: at this batch of 1 x 1024 with heads of 128 the
chunk-local work lowers to the ``gdn_chunk_*`` kernels, interpreted on the
CPU), and what ISSUE 45 gave for ``ling-3.0-flash-d7``, the family it
brought (a later PR that means to leave it alone leaves both alone). The
three hybrid configurations' steps were recorded anew at ISSUE 47, whose
convolution kernels their mixers take at these widths (``conv_silu_*``,
interpreted on the CPU); their trees and the other three entries are as
they were. ``trinity-mini-d5`` is as ISSUE 50 brought it (window and
global attention layers, each layer between two norms; on the CPU its
attention lowers to the jnp path with the window as a mask), and ISSUE 50
recorded the Nemotron and Ling steps anew, their trees as they were: each
holds a quantised leaf whose rows are no whole blocks of 128 (the experts'
``w_up`` ``[8, 2688, 1856]``, the latent layer's ``wq`` ``[2560, 32,
192]``), and ``ops/quantized_optim._to_blocks`` now pads such a row to
whole blocks so that no block of int8 moments crosses a row. ISSUE 51
(a recomputed layer is a ``jax.checkpoint`` whose policy keeps what the
attention kernels' forward rule names, where it was a bare one) left all seven
entries as they were: the five configurations without ``remat`` never meet
the wrapper, and on the CPU the attention of ``ling-3.0-flash-d7`` and
``trinity-mini-d5`` lowers to the jnp path, which holds no such name, so
their recomputed layers keep their inputs alone as before (the policy is
one object a process: a policy a wrapper would split every layer's inner
functions anew and double the functions of the lowered text). ISSUE 52
recorded the steps of the four configurations that hold a share of the
experts anew (``nemotron3-nano-30b-a3b-d9``, ``qwen3-next-80b-a3b-d4``,
``ling-3.0-flash-d7``, ``trinity-mini-d5``), their trees as they were:
the first round of ``parallel/moe._moe_share`` is differentiated in line
and keeps what its backward pass reads, only the rounds past it stay in
the hand-differentiated loop, and the two recomputing ones' policy saves
the round's three names beside the attention kernels'. The other three
entries (no share: ``_moe_dropless`` or no experts) are as they were.
ISSUE 53 brought ``phi4-mini-flash-d6`` (Mamba-1 scans, whose kernels are
interpreted on the CPU at these widths, differential attention on the jnp
path, a memory unit and a cross-attention that read another layer's
values, which ``forward``'s loop now carries beside the residual stream)
and left the seven entries before it as they were.
Made by running this file there:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python tests/lowering_fingerprint.py

``test_nemotron_h.py`` holds the tree to it: a change to the model code
that is meant to leave those configurations alone leaves both alone. A PR
that means to change their step records the file anew and says so.
"""

import hashlib
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = (
    "gpt2-124m", "gpt2-xl-d12", "olmoe-1b-7b-d2",
    "nemotron3-nano-30b-a3b-d9", "qwen3-next-80b-a3b-d4",
    "ling-3.0-flash-d7", "trinity-mini-d5", "phi4-mini-flash-d6",
)


def fingerprint(name: str) -> dict:
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.train import TrainState, build_train_step
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.elastic.optimizer import build_optimizer

    with open(
        os.path.join(ROOT, "benchmark", "configs", f"{name}.json")
    ) as f:
        config = json.load(f)
    cfg = TransformerConfig(**config["model"])
    opt = dict(config["optimizer"])
    tx = build_optimizer(opt.pop("name"), **opt)
    mesh = build_mesh(MeshConfig(), jax.devices()[:1])

    def state():
        params = init_params(jax.random.PRNGKey(0), cfg)
        return TrainState(
            step=jnp.zeros((), jnp.int32), params=params,
            opt_state=tx.init(params),
        )

    abstract = jax.eval_shape(state)
    tree = "\n".join(
        f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}"
        for path, leaf in jax.tree_util.tree_leaves_with_path(
            abstract.params
        )
    )
    x = jax.ShapeDtypeStruct((1, 1024), jnp.int32)
    text = build_train_step(cfg, mesh, tx).lower(abstract, x, x).as_text()
    return {
        "tree": hashlib.sha256(tree.encode()).hexdigest(),
        "step": hashlib.sha256(text.encode()).hexdigest(),
    }


if __name__ == "__main__":
    json.dump({n: fingerprint(n) for n in NAMES}, sys.stdout, indent=1)
    print()
