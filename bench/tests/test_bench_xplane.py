"""The op-name decoder on the trace recorded on a TPU v5e, and the scope
reduction on hand-made intervals."""

import re
from pathlib import Path

import pytest

from bench import trace, xplane

DATA = Path(__file__).resolve().parent / "data"
KV_SMALL = DATA / "kv_small.xplane.pb"


@pytest.fixture(scope="module")
def small():
    return trace.reduce_file(str(KV_SMALL)), xplane.tf_ops(str(KV_SMALL))


def _by_hlo_name(ops_tf: dict) -> dict:
    return {name.split(" = ")[0].lstrip("%"): tf for name, tf in ops_tf.items()}


def test_tf_op_of_the_recorded_ops(small):
    _, ops_tf = small
    assert list(ops_tf) == [0]
    got = _by_hlo_name(ops_tf[0])
    assert got["fusion.13"] == "jit(run_ops)/scatter:"
    assert got["sort.0"] == "jit(run_ops)/jit(argsort)/sort:"
    assert got["msl_onepass_kernel_call.1"] == (
        "jit(run_ops)/jit(msl_onepass_kernel_call)/pallas_call:")
    assert got["copy.37"] == "jit(run_ops)/reshape:"


def test_event_names_are_those_of_the_reduction(small):
    summary, ops_tf = small
    windowed = {n for n, _, _ in summary.ops[0]}
    named = windowed & set(ops_tf[0])
    # most ops have a source op; the undonated table's slices and the
    # parameters' prefetch copies do not
    assert len(named) > len(windowed) // 2
    assert not any(n.startswith("%slice-start") for n in named)


def test_scope_seconds_on_the_recorded_trace(small):
    summary, ops_tf = small
    engine = summary.seconds("jit_run_ops", "modules")
    # the trace predates the phase scopes: a jit segment stands in for one
    sort = xplane.scope_seconds(summary, ops_tf, "jit(argsort)",
                                "jit_run_ops")
    assert 0 < sort < engine
    names = [n for n, tf in ops_tf[0].items() if "/jit(argsort)/" in tf]
    assert any(n.startswith("%sort.0 ") for n in names)
    want = summary.seconds("|".join("^" + re.escape(n) + "$" for n in names))
    assert sort == pytest.approx(want, rel=1e-9)
    assert xplane.scope_seconds(summary, ops_tf, "msl.scatter",
                                "jit_run_ops") == 0.0
    assert xplane.scope_seconds(summary, ops_tf, "jit(argsort)",
                                "jit_decode_step") == 0.0


def test_scope_seconds_by_hand():
    ops = {0: [("a", 0, 10), ("b", 5, 15), ("c", 20, 30), ("a", 40, 50)],
           1: [("a", 0, 4), ("c", 0, 8)]}
    modules = {0: [("jit_run_ops(1)", 0, 35), ("jit_other(2)", 40, 50)],
               1: [("jit_run_ops(1)", 0, 6)]}
    summary = trace.Summary((0, 100), ops, modules, [])
    ops_tf = {0: {"a": "jit(run_ops)/msl.order/sort:",
                  "b": "jit(run_ops)/msl.order/gather:",
                  "c": "jit(run_ops)/msl.scatter/scatter:"},
              1: {"a": "jit(run_ops)/msl.order/sort:",
                  "c": "jit(run_ops)/msl.scatter/scatter:"}}
    # chip 0: a and b overlap (0-15), a's second run is another program;
    # chip 1: a (0-4); so (15 + 4) / 2 chips
    assert xplane.scope_seconds(summary, ops_tf, "msl.order",
                                "jit_run_ops") == pytest.approx(9.5e-9)
    # chip 1's scatter is cut at its program's end (6)
    assert xplane.scope_seconds(summary, ops_tf, "msl.scatter",
                                "jit_run_ops") == pytest.approx(8e-9)
    assert xplane.scope_seconds(summary, ops_tf, "msl.chain",
                                "jit_run_ops") == 0.0
    # the bisection agrees with a plain scan of every run
    runs = trace.merge([(0, 10), (20, 30), (30, 35), (50, 60)])
    ivs = [(5, 25), (12, 18), (-5, 2), (34, 55), (60, 70)]
    scan = [(max(s, a), min(e, b)) for s, e in ivs for a, b in runs
            if min(e, b) > max(s, a)]
    assert trace.clip(ivs, runs) == scan
    empty = trace.Summary((0, 1), {}, {}, [])
    assert xplane.scope_seconds(empty, {}, "msl.order", "jit_run_ops") == 0.0


PHASES = ["msl.order", "msl.table", "msl.gather", "msl.chain", "msl.scatter"]


def test_phases_of_a_scoped_trace():
    """A trace recorded on a TPU v5e with the phase scopes in the program
    (the kv cell cut to 65,536 sets, a 0.3-s window, 48 engine calls):
    every phase has device time, no op falls in two phases, and the ops
    in none are the residual between the phases and the engine's ops."""
    path = str(DATA / "kv_phases_small.xplane.pb")
    summary, ops_tf = trace.reduce_file(path), xplane.tf_ops(path)
    program = "jit_run_ops"
    assert summary.count(program) == 48
    phases = {p: xplane.scope_seconds(summary, ops_tf, p, program)
              for p in PHASES}
    assert all(v > 0 for v in phases.values()), phases
    runs = trace.merge([(s, e) for n, s, e in summary.modules[0]
                        if program in n])
    ops = summary.ops[0]
    busy = trace.union_ns(trace.clip([(s, e) for _, s, e in ops], runs))
    unscoped = trace.union_ns(trace.clip(
        [(s, e) for n, s, e in ops if "/msl." not in ops_tf[0].get(n, "")],
        runs))
    assert sum(phases.values()) == pytest.approx((busy - unscoped) * 1e-9,
                                                 rel=1e-9)
    # at this size the residual is a few ops XLA made without a source op
    # (the table's async slices among them); it is under 5% of the engine
    engine = summary.seconds(program, "modules")
    assert 0 < engine - sum(phases.values()) < 0.05 * engine
    assert any(n.startswith("%slice-start") and n not in ops_tf[0]
               for n, _, _ in ops)
