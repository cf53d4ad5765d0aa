#!/usr/bin/env python3
"""How a configuration's ``reference_check.tolerance`` is set and what its
gradients read, at the published widths on the chip, in ONE process that
owns it (run by hand; nothing here is a speed):

    python benchmark/tests/reference_on_chip.py <configuration> <seq> \\
        <seed> [<seed> ...] [--grads <seed>] [--controls N] [--dtype D]
        [--set <model field>=<JSON value> ...]

(``<configuration>``: a name under ``configs/``, or a path to such a file.)

For every seed, as ``worker.py`` makes them (weights from
``PRNGKey(seed % (2**31 - 1))``, the corpus's first row from the seed): the
program's forward loss (``loss_fn``, the timed path's own precision) beside
the plain reference's, float32 at ``highest``. For the first ``N`` seeds
(``--controls``, default 3) the reference again with every matmul operand
rounded to bfloat16 and to ``float8_e4m3fn``, the precisions below the one
the configuration states: the tolerance has to lie between what the program
gives and what the second control gives. With ``--grads``, the gradients
of both at that seed, leaf by leaf: the cosine, and the ratio of the
norms, beside the norm of the reference's (and, of a leaf of at most 64
elements, both vectors). ``--dtype float32`` runs the
program's side in float32 instead of the configuration's activation dtype
(its matmuls at the backend's default precision, which on the TPU is still
bfloat16 passes; add ``--highest`` for float32 products): what is left
between the two is then the order of the sums and not the rounding.
``--set`` overrides a field of the ``model`` group on the program's side
(``--set gdn_chunk=32``, ``--set remat=true``). One JSON line a reading on
stdout, flushed.
"""

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)


def say(**kw):
    print(json.dumps(kw), flush=True)


def main(argv) -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from corpus import Corpus
    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.transformer import init_params, loss_fn

    parser = argparse.ArgumentParser()
    parser.add_argument("configuration")
    parser.add_argument("seq", type=int)
    parser.add_argument("seeds", type=int, nargs="+")
    parser.add_argument("--grads", type=int, default=None)
    parser.add_argument("--controls", type=int, default=3)
    parser.add_argument("--dtype", default=None)
    parser.add_argument("--highest", action="store_true")
    parser.add_argument("--set", action="append", default=[])
    args = parser.parse_args(argv)
    name, seq, seeds = args.configuration, args.seq, args.seeds
    grads_seed, controls = args.grads, args.controls
    path = name if name.endswith(".json") else os.path.join(
        BENCH, "configs", f"{name}.json"
    )
    with open(path) as f:
        config = json.load(f)
    model = dict(config["model"], max_seq_len=seq)
    if args.dtype:
        model["dtype"] = args.dtype
    for field in args.set:
        key, value = field.split("=", 1)
        model[key] = json.loads(value)
    cfg = TransformerConfig(**model)
    spec = importlib.util.spec_from_file_location(
        "plain_reference",
        os.path.join(BENCH, "references", f"{config['reference']}.py"),
    )
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    say(device=jax.devices()[0].device_kind, config=name, seq=seq,
        dtype=cfg.dtype, tolerance=config["reference_check"]["tolerance"])

    def inputs(seed):
        params = jax.jit(lambda k: init_params(k, cfg))(
            jax.random.PRNGKey(seed % (2**31 - 1))
        )
        row = Corpus(1, seq, cfg.vocab_size, seed).data
        return params, row[:, :-1], row[:, 1:]

    def program_loss(p, x, y):
        if not args.highest:
            return loss_fn(p, x, y, cfg, None)
        with jax.default_matmul_precision("highest"):
            return loss_fn(p, x, y, cfg, None)

    program = jax.jit(program_loss)
    plain = jax.jit(ref.loss)

    def rounded(dtype):
        """The reference with every matmul operand rounded to ``dtype``
        (and back to float32: the products and sums stay float32)."""
        def to(a):
            return a.astype(dtype).astype(jnp.float32)

        def loss(p, x, y):
            keep = ref.matmul, ref.einsum
            ref.matmul = lambda a, b: keep[0](to(a), to(b))
            ref.einsum = lambda s, a, b: keep[1](s, to(a), to(b))
            try:
                return ref.loss(p, x, y)
            finally:
                ref.matmul, ref.einsum = keep

        return jax.jit(loss)

    lower = {
        "bfloat16": rounded(jnp.bfloat16),
        "float8_e4m3fn": rounded(jnp.float8_e4m3fn),
    }
    for n, seed in enumerate(seeds):
        params, x, y = inputs(seed)
        got, want = float(program(params, x, y)), float(plain(params, x, y))
        line = dict(seed=seed, program_loss=got, reference_loss=want,
                    abs_diff=abs(got - want))
        if n < controls:
            for dtype, fn in lower.items():
                line[f"reference_in_{dtype}_abs_diff"] = abs(
                    float(fn(params, x, y)) - want
                )
        say(**line)
        del params
    if grads_seed is None:
        return 0
    params, x, y = inputs(grads_seed)
    g_got = jax.jit(jax.grad(lambda p: program_loss(p, x, y)))(params)
    g_got = jax.tree_util.tree_map(np.asarray, g_got)
    g_want = jax.jit(jax.grad(lambda p: ref.loss(p, x, y)))(params)
    for (path, a), b in zip(
        jax.tree_util.tree_leaves_with_path(g_got),
        jax.tree_util.tree_leaves(g_want),
    ):
        a = np.asarray(a, np.float64).ravel()
        b = np.asarray(b, np.float64).ravel()
        na, nb = np.linalg.norm(a), np.linalg.norm(b)
        line = dict(
            leaf=jax.tree_util.keystr(path), elements=int(a.size),
            cosine=float(a @ b / (na * nb)) if na * nb else None,
            norm_ratio=float(na / nb) if nb else None,
            reference_norm=float(nb),
        )
        if a.size <= 64:  # a per-head vector: both sides, whole
            line.update(program=a.tolist(), reference=b.tolist())
        say(**line)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
