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
and left the seven entries before it as they were. ISSUE 56 recorded the
steps of ``qwen3-next-80b-a3b-d4`` and ``ling-3.0-flash-d7`` anew, their
trees as they were: the delta rule's serial pass names what its forward
rule hands its backward rule, ``_delta_rule`` its result and the mixer the
``[q | k | v]`` its convolution reads (``ops/gated_delta.KEPT``). What
both steps were before is kept beside them as ``step_without_names``, and
``without_names`` gives it still: the step lowered with those names taken
off. In the Qwen3-Next step (no ``remat``) a name lowers to nothing and
the text is the old one operation for operation; each name is an equation
all the same, which the lowering emits as a function of its own before it
inlines it, and that moves the numbers it appends to an inner function's
name to tell its copies apart (``@triu_68`` -> ``@triu_73``): with those
cut back to the name (``inner_numbers_off``) the text with the names is
the text without them, which ``test_nemotron_h.py`` holds it to. The Ling
step (``remat``) is another program with the names: its recomputed KDA
layers keep the named arrays and hold each forward kernel and the pass
once. The other six entries are as they were. ISSUE 57 brought
``ouro-2.6b-d6`` (a looped model: ``forward`` runs a pattern's walk
``ut_steps`` times as a ``lax.scan`` over the same leaves, and ``loss_fn``
takes an exit through the head after every pass) and left the eight entries
before it as they were: ``ut_steps`` 1 is the walk once, no gate leaf, no
exit arithmetic. ISSUE 58 recorded the ``ouro-2.6b-d6`` step anew, its tree
as it was: the exits' head and loss are one function with its own backward
rule (``models/transformer.exits_nll``: the stopping distribution first,
then a ``lax.scan`` over the exits that makes softmax minus one-hot beside
each loss and both gradient products from it, where a ``lax.map`` of
``jax.checkpoint``ed exits stood). The other eight entries, whose models
run their layers once and never meet ``ut_exits``, are as they were.
ISSUE 59 brought ``mistral-small-4-119b-d4`` (a latent attention whose
query passes a latent of its own, rotated by a YaRN table on interleaved
pairs, the query scaled by its position) and left the nine entries before
it as they were: ``q_latent_dim`` 0 is the whole ``wq``, and with no
``rope_scaling``, ``rope_pairs`` or ``attn_pos_scale_beta`` ``_rope`` makes
``theta``'s own table on rotate-half pairs from the same operations.
ISSUE 61 recorded the steps of the eight configurations that train with
``adamw_8bit`` anew (and the two ``step_without_names`` beside them, which
are the same steps with the delta rule's names taken off), their trees as
they were, and the state's too: ``build_train_step`` calls the
transformation's second entry (``InPlaceTransformation.update_and_apply``)
where one device owns a donating step, which on the CPU is ``update``, the
scale and ``apply_updates`` leaf by leaf in place of tree by tree, no
kernel (a ``TILES`` leaf's one-pass kernel ``_q8_adam_step`` is a TPU's),
and the shared ``_sqrt_map_quant`` / ``_sqrt_map_dequant`` make the same
codes and values from fewer operations.
The two GPT-2 entries (fp32 ``adamw``: no second entry, the two lines the
step had) are byte for byte what they were.
ISSUE 63 recorded the steps of ``nemotron3-nano-30b-a3b-d9``,
``qwen3-next-80b-a3b-d4`` and ``ling-3.0-flash-d7`` anew (and the two
``step_without_names`` beside them), their trees as they were: the gated
norm after their mixers' scans goes through ``ops/mamba2.gated_norm``,
which at these widths (4096 channels in groups of 512 or heads of 128)
takes the ``gated_norm_*`` kernels of ``ops/gated_norm_kernels.py``,
interpreted on the CPU. The seven configurations without an ``M`` or ``G``
layer never reach it and are byte for byte what they were.
ISSUE 64 brought ``olmo-hybrid-7b-d4`` (delta-rule heads of 96 / 192, no
whole lane tiles, which the ``gdn_chunk_*`` kernels read head-major and
invert by halves at a write strength up to 2; a gated norm over groups of
192; an attention layer and its feed-forward with their norms on the
output) and left the ten entries before it byte for byte as they were:
``gdn_beta_scale`` 1 is no product and the product form, no
``reordered_norm_kinds`` is a ``norm`` leaf an entry, heads of whole tiles
are read token-major as before.
ISSUE 65 recorded the steps of ``qwen3-next-80b-a3b-d4``,
``ling-3.0-flash-d7`` and ``olmo-hybrid-7b-d4`` anew (and the
``step_without_names`` beside each), their trees as they were: where the
chunk-local work runs in kernels the serial pass between its two stretches
does too (``delta_state_pass`` / ``delta_state_pass_rev``, interpreted on
the CPU), where it was a ``lax.scan`` each way and einsums over all chunks
after the reversed one. The eight configurations without a ``G`` layer
never reach ``chunk_state_pass`` and are byte for byte what they were.
ISSUE 66 recorded the step of ``nemotron3-nano-30b-a3b-d9`` anew, its tree
as it was: at these widths (64 heads of 64 in 8 groups, a state of 128,
chunks of 128) its four Mamba-2 scans take the ``ssd_scan_*`` kernels of
``ops/ssd_kernels.py``, interpreted on the CPU, where they were
``ssd_chunked`` under a ``jax.checkpoint``. The ten configurations without
an ``M`` layer never reach the rule and are byte for byte what they were.
ISSUE 67 brought ``sdar-30b-a3b-d8`` (a model trained by diffusion over
blocks: ``loss_fn`` noises the row, feeds it twice and weighs a token's
cross-entropy; on the CPU its attention lowers to the jnp path with the
block-diffusion rule as a mask) and left the eleven entries before it byte
for byte as they were: with no ``objective`` ``loss_fn`` takes no other
branch, ``token_nll`` without ``token_weights`` multiplies nothing, and
``forward`` makes ``arange(T)`` as before. After review it recorded the
steps of the nine configurations that train with ``adamw_8bit`` anew (and
the three ``step_without_names`` beside them), their trees as they were:
``ops/quantized_optim._sqrt_map_quant`` gives a second moment the least
code 1 where it rounded to 0 (the floor stands where the guard against
values below 0 stood, one product a block more; the first moments' codes
are made as before),
because the new cell's 1 / t loss weights showed what code 0 beside a
live first moment does (PERF.md §6, PR 67). The two GPT-2 entries (fp32
``adamw``) are byte for byte what they were.
Made by running this file there:

    JAX_PLATFORMS=cpu PYTHONPATH=<checkout> python tests/lowering_fingerprint.py

``test_nemotron_h.py`` holds the tree to it: a change to the model code
that is meant to leave those configurations alone leaves both alone. A PR
that means to change their step records the file anew and says so.
"""

import functools
import hashlib
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAMES = (
    "gpt2-124m", "gpt2-xl-d12", "olmoe-1b-7b-d2",
    "nemotron3-nano-30b-a3b-d9", "qwen3-next-80b-a3b-d4",
    "ling-3.0-flash-d7", "trinity-mini-d5", "phi4-mini-flash-d6",
    "ouro-2.6b-d6", "mistral-small-4-119b-d4", "olmo-hybrid-7b-d4",
    "sdar-30b-a3b-d8",
)


def inner_numbers_off(text: str) -> str:
    """A lowered text with the numbers cut off that tell the copies of an
    inner function apart (``@triu_73`` -> ``@triu``): they count every
    equation lowered before, those that lower to nothing too."""
    return re.sub(r"(@\w+?)_\d+\b", r"\1", text)


def configuration(name: str) -> dict:
    with open(
        os.path.join(ROOT, "benchmark", "configs", f"{name}.json")
    ) as f:
        return json.load(f)


@functools.cache  # a step lowers in 20 to 50 s, and two tests read it
def lowered(name: str) -> tuple:
    """The parameter tree (paths, shapes, dtypes) and the text of the
    lowered train step of a benchmark configuration."""
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.train import TrainState, build_train_step
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.elastic.optimizer import build_optimizer

    config = configuration(name)
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
    step = build_train_step(cfg, mesh, tx).lower(abstract, x, x)
    return tree, step.as_text()


def without_names(name: str) -> str:
    """The step's text with the delta rule's names taken off
    (``ops/gated_delta.KEPT``: ``checkpoint_name`` an identity while it is
    traced): the program the configuration had before ISSUE 56."""
    from dlrover_tpu.ops import gated_delta

    name_of = gated_delta.checkpoint_name
    gated_delta.checkpoint_name = lambda x, name: x
    try:
        return lowered.__wrapped__(name)[1]
    finally:
        gated_delta.checkpoint_name = name_of


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()


def fingerprint(name: str) -> dict:
    tree, step = lowered(name)
    return {"tree": _sha(tree), "step": _sha(step)}


if __name__ == "__main__":
    record = {n: fingerprint(n) for n in NAMES}
    for n in NAMES:
        if "G" in (configuration(n)["model"].get("layer_pattern") or ""):
            record[n]["step_without_names"] = _sha(without_names(n))
    json.dump(record, sys.stdout, indent=1)
    print()
