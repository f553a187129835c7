"""The trace reduction on a trace recorded on a TPU v5e (a kv cell at
65,536 sets, 0.3 s, profiler host level 1), and on hand-made intervals."""

from pathlib import Path

import pytest

from bench import common, trace

DATA = Path(__file__).resolve().parent / "data" / "kv_small.xplane.pb"


@pytest.fixture(scope="module")
def summary():
    return trace.reduce_file(str(DATA))


def test_window_busy_and_programs(summary):
    assert list(summary.ops) == [0]
    assert 0.25 < summary.window_s < 0.35
    assert 0 < summary.busy_s <= summary.window_s
    assert summary.count("jit_run_ops") == 80
    engine = summary.seconds("jit_run_ops", "modules")
    kernel = summary.seconds("msl_onepass_kernel_call")
    assert 0 < kernel < engine <= summary.window_s
    assert {n for n, _, _ in summary.host} == {"window", "dispatch", "fetch",
                                               "gen_wait"}


def test_breakdown_and_gaps(summary):
    b = summary.breakdown()
    ops = [v for _, v in b["device_ops"]]
    assert 0 < len(ops) <= 10 and ops == sorted(ops, reverse=True)
    assert any("msl_onepass_kernel_call" in n for n, _ in b["device_ops"])
    gaps = summary.idle_gaps()
    idle = sum(g for _, g in gaps)
    assert idle == pytest.approx(summary.window_s - summary.busy_s, rel=1e-6)
    assert {n for n, _ in gaps} <= {"window", "dispatch", "fetch", "gen_wait"}
    assert len(b["idle_gaps"]) <= 10


def test_kv_readers_on_the_chip_trace(summary):
    counts = {"kv_batches": 80, "kv_batch_queries": 8192,
              "kv_distinct_sets_mean": 7000.0, "kv_row_bytes": 96,
              "kv_query_bytes": 16, "kv_result_bytes": 26, "chips": 1}
    view = trace.RunView(counts, summary, {"hbm_bytes_per_s": 819e9,
                                           "bf16_flops": 197e12}, {})
    got = {name: r.read(view) for name, r in common.metric_readers().items()}
    assert got["kv_engine_ms"] == pytest.approx(
        summary.seconds("jit_run_ops", "modules") / 80 * 1e3)
    assert 0 < got["kv_kernel_roofline"] <= 100
    assert 0 <= got["kv_device_idle"] < 100
    for name in ("kv_route_ms", "prefix_hit_share", "decode_mfu",
                 "decode_roofline", "prefill_us_per_token",
                 "serve_device_idle"):
        assert got[name] is None


def test_interval_helpers():
    assert trace.merge([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    assert trace.union_ns([(0, 2), (1, 3), (10, 11)]) == 4
    assert trace.clip([(0, 10)], [(2, 3), (5, 6)]) == [(2, 3), (5, 6)]
    assert trace.op_label("%copy.39 = s32[8,3]{0,1:T(8,128)} copy(s32[8,3] "
                          "%table.1)") == "copy.39 copy"
