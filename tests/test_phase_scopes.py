"""The engine's phases are named scopes of the program.

Every op of the one-pass engine's program sits under exactly one of the
five ``msl.*`` scopes (the sharded engine adds ``msl.route``), so a
profiler trace can attribute each device op of a batch to one phase by its
op name.  The check walks the lowered StableHLO: an op's location carries
its scope path, and ops of a called function inherit the scope of each
call site (that is the op name XLA gives them when it inlines the call).
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import OP_ACCESS, make_batched_engine
from repro.core.multistep import MSLRUConfig, init_table

PHASES = {"msl.order", "msl.table", "msl.gather", "msl.chain",
          "msl.scatter"}
CFG = MSLRUConfig(num_sets=64, m=2, p=4, value_planes=2)
B = 32

# no device op of their own: constants are folded into their users,
# terminators and calls are gone once XLA inlines the callee
_NOT_OPS = {"stablehlo.constant", "func.return", "stablehlo.return"}


def _scopes(loc) -> list:
    m = re.match(r'loc\("([^"]*)"', str(loc))
    name = m.group(1) if m else ""
    return re.findall(r"(?:^|/)(msl\.[a-z]+)(?=/|$)", name)


def _phase_of_every_op(module) -> list:
    """[(op name, [scopes on its path])] for every op of ``main`` and,
    through each call site, of the functions it calls."""
    funcs = {str(f.attributes["sym_name"]).strip('"'): f
             for f in module.body.operations}
    out = []

    def walk(block, above):
        for op in block.operations:
            name = op.operation.name
            path = above + _scopes(op.location)
            if name == "func.call":
                callee = str(op.attributes["callee"]).lstrip("@")
                walk(funcs[callee].regions[0].blocks[0], path)
            elif name not in _NOT_OPS:
                out.append((name, path))
            for region in op.operation.regions:
                for inner in region.blocks:
                    walk(inner, above)

    walk(funcs["main"].regions[0].blocks[0], [])
    return out


def _args(with_ops=True):
    rng = np.random.default_rng(0)
    keys = jnp.asarray(rng.integers(1, 500, (B, 1)), jnp.int32)
    vals = jnp.asarray(rng.integers(0, 9, (B, 2)), jnp.int32)
    ops = jnp.full((B,), OP_ACCESS, jnp.int32) if with_ops else None
    return init_table(CFG), keys, vals, ops


def _check(module, phases):
    ops = _phase_of_every_op(module)
    assert ops
    stray = [(n, p) for n, p in ops if len(p) != 1 or p[0] not in phases]
    assert not stray, stray[:10]
    by_kind = {}
    for n, (p,) in ops:
        by_kind.setdefault(n, set()).add(p)
    assert by_kind["stablehlo.sort"] <= {"msl.order"}
    return by_kind


@pytest.mark.parametrize("use_kernel,with_ops,chain", [
    (False, True, False), (False, False, False), (False, True, True),
    (True, True, False), (True, False, False)],
    ids=["xla-ops", "xla-access", "xla-chain", "kernel-ops",
         "kernel-access"])
def test_every_engine_op_is_in_one_phase(use_kernel, with_ops, chain):
    run = make_batched_engine(CFG, engine="onepass", use_kernel=use_kernel,
                              interpret=True if use_kernel else None)
    table, keys, vals, ops = _args(with_ops)
    chain_ids = jnp.arange(B, dtype=jnp.int32) if chain else None
    lowered = jax.jit(run).lower(table, keys, vals, ops, chain_ids)
    by_kind = _check(lowered.compiler_ir("stablehlo"), PHASES)
    # the table's set-column view, its gather and its scatter
    assert "msl.table" in by_kind["stablehlo.transpose"]
    assert "msl.gather" in by_kind["stablehlo.gather"]
    assert "msl.scatter" in by_kind["stablehlo.scatter"]


def test_every_sharded_engine_op_is_in_one_phase_or_the_route():
    from repro.core.sharded import make_sharded_engine
    from repro.launch.mesh import make_mesh_compat
    mesh = make_mesh_compat((1,), ("cache",))
    run = make_sharded_engine(CFG, mesh, cap="full", engine="onepass")
    table, keys, vals, ops = _args()
    lowered = jax.jit(run).lower(table, keys, vals, ops)
    by_kind = _check(lowered.compiler_ir("stablehlo"),
                     PHASES | {"msl.route"})
    # one device: the all_to_all is elided, the pack's scatter stays
    assert "msl.route" in by_kind["stablehlo.scatter"]
