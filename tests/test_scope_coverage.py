"""The program-side guard of ``benchmark/scopes.py``'s table: a new kind
of layer cannot arrive unscoped, and a new scope cannot arrive unknown.

The train step of each family's toy configuration (the benchmark's CPU
rehearsal configurations, ``benchmark/tests/rehearsal/configs``) is
lowered with locations, and every ``dot_general``, convolution, ``while``
and custom call of it must lie under a scope the table gives a part. An
operation of a function the lowering keeps apart (a ``lax.scan`` body, a
cached inner ``jit``) carries a path relative to that function, so the
path it is judged by is its call sites' paths with its own at the end, as
the compiler hands it to the operation when it inlines the call.
"""

import glob
import json
import os
import re
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

import scopes  # noqa: E402

CONFIGS = os.path.join(ROOT, "benchmark", "tests", "rehearsal", "configs")
FAMILIES = sorted(
    os.path.basename(p)[:-5] for p in glob.glob(os.path.join(CONFIGS, "*.json"))
)
HEAVY = ("dot_general", "convolution", "while", "custom_call", "ragged_dot")


def _lowered(name):
    import jax
    import jax.numpy as jnp

    from dlrover_tpu.models.config import TransformerConfig
    from dlrover_tpu.models.train import TrainState, build_train_step
    from dlrover_tpu.models.transformer import init_params
    from dlrover_tpu.parallel.mesh import MeshConfig, build_mesh
    from dlrover_tpu.trainer.elastic.optimizer import build_optimizer

    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
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

    x = jax.ShapeDtypeStruct((2, 64), jnp.int32)
    return build_train_step(cfg, mesh, tx).lower(jax.eval_shape(state), x, x)


def _name(op) -> str:
    """The name-stack part of an operation's location: the quoted names
    that open it, up to the first file or call site."""
    text, names = str(op.location)[len("loc("):], []
    while text.startswith('"'):
        name, _, text = text[1:].partition('"')
        if name.endswith(".py"):
            break
        names.append(name)
        if not text.startswith("("):
            break
        text = text[1:]
    return "/".join(names)


def _paths(module):
    """(operation, path) of every heavy operation, a called function's
    under each of its call sites."""
    from jax._src.lib.mlir import ir

    funcs = {}
    for func in module.body.operations:
        if func.operation.name != "func.func":
            continue
        ops, calls = [], []

        def visit(op, ops=ops, calls=calls):
            kind = op.name
            if kind == "func.call":
                callee = ir.FlatSymbolRefAttr(op.attributes["callee"]).value
                calls.append((callee, _name(op)))
            elif kind.split(".")[-1] in HEAVY:
                ops.append((kind, _name(op)))
            return ir.WalkResult.ADVANCE

        func.operation.walk(visit)
        funcs[ir.StringAttr(func.attributes["sym_name"]).value] = (ops, calls)

    def under(sym, prefix):
        ops, calls = funcs[sym]
        for kind, name in ops:
            yield kind, f"{prefix}/{name}"
        for callee, name in calls:
            yield from under(callee, f"{prefix}/{name}")

    return list(under("main", ""))


@pytest.mark.parametrize("family", FAMILIES)
def test_every_heavy_operation_of_a_step_lies_under_a_known_part(family):
    paths = _paths(_lowered(family).compiler_ir())
    assert len(paths) > 20
    outside = sorted(
        {(kind, path) for kind, path in paths
         if scopes.classify(path)[0] == scopes.UNSCOPED}
    )
    assert not outside, outside[:10]
    unknown = {u for _k, path in paths for u in scopes.classify(path)[3]}
    assert not unknown


def test_the_families_are_the_rehearsals():
    assert len(FAMILIES) >= 7 and "toy" in FAMILIES


def _opened_scopes():
    found = {}
    pattern = re.compile(r'named_scope\(\s*"scope/([^"]+)"')
    for path in glob.glob(
        os.path.join(ROOT, "dlrover_tpu", "**", "*.py"), recursive=True
    ):
        with open(path) as f:
            for name in pattern.findall(f.read()):
                found.setdefault(name, os.path.relpath(path, ROOT))
    return found


def test_every_scope_the_program_opens_is_in_the_table():
    opened = _opened_scopes()
    assert len(opened) >= 40
    assert {n: p for n, p in opened.items() if n not in scopes.KNOWN} == {}


def test_every_scope_of_the_table_is_opened_by_the_program():
    """Itself, or (``layer/moe``) as the scopes under it."""
    opened = set(_opened_scopes())
    stale = {
        name for name in scopes.KNOWN
        if name not in opened
        and not any(o.startswith(name + "/") for o in opened)
    }
    assert stale == set()
