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


def _host_at_scan(summary, t):
    """The innermost host span open at ``t`` by a scan of every span."""
    best, size = trace.WINDOW, None
    for n, s, e in summary.host:
        if s <= t < e and n != trace.WINDOW and (size is None or e - s < size):
            best, size = n, e - s
    return best


def _clip_scan(intervals, within):
    return [(max(s, a), min(e, b)) for s, e in intervals for a, b in within
            if min(e, b) > max(s, a)]


@pytest.mark.parametrize("name", ["kv_small", "kv_phases_small"])
def test_sweeps_give_the_scans_answers(name):
    """On the recorded traces the sweep and the bisection answer exactly
    as a scan of every span and every interval does."""
    summary = trace.reduce_file(str(DATA.parent / f"{name}.xplane.pb"))
    busy = trace.merge([(s, e) for _, s, e in summary.ops[0]])
    gaps, t = [], summary.window[0]
    for s, e in busy + [(summary.window[1], summary.window[1])]:
        if s > t:
            gaps.append((_host_at_scan(summary, (t + s) / 2), (s - t) * 1e-9))
        t = max(t, e)
    assert summary.idle_gaps() == gaps
    assert summary.breakdown()["idle_gaps"] == [
        list(x) for x in sorted(gaps, key=lambda x: -x[1])[:10]]
    edges = sorted({x for _, s, e in summary.host for x in (s, e, s - 1)})
    assert summary.hosts_at(edges) == [_host_at_scan(summary, x)
                                       for x in edges]
    ops = [(s, e) for _, s, e in summary.ops[0]]
    for span in ("dispatch", "fetch", "gen_wait"):
        ivs = trace.merge(summary.spans(span))
        assert trace.clip(ops, ivs) == _clip_scan(ops, ivs)
        assert summary.busy_within(summary.spans(span)) == (
            trace.union_ns(_clip_scan(ops, ivs)) * 1e-9,
            sum(e - s for s, e in ivs) * 1e-9)


def test_reduction_of_a_long_window_is_quick():
    """100,000 idle gaps against 30,000 nested host spans: a scan of every
    span for every gap would take thousands of seconds."""
    import time
    blocks, n_ops = 10_000, 100_001
    ops = [("op", k * 100.0, k * 100.0 + 50) for k in range(n_ops)]
    end = ops[-1][2]
    host = [("window", 0.0, end)]
    for j in range(blocks):
        b = j * 1000.0
        host += [("dispatch", b, b + 900), ("msl.access", b + 100, b + 800),
                 ("fetch", b + 300, b + 400)]
    summary = trace.Summary((0.0, end), {0: ops}, {}, host)
    t = time.perf_counter()
    gaps = summary.idle_gaps()
    busy, length = summary.busy_within(summary.spans("msl.access"))
    assert time.perf_counter() - t < 5
    assert len(gaps) == 100_000

    def want(k):                      # the gap after op k, by its midpoint
        o = (k * 100 + 75) % 1000
        if 300 <= o < 400:
            return "fetch"
        if 100 <= o < 800:
            return "msl.access"
        return "dispatch" if o < 900 else "window"
    assert [n for n, _ in gaps] == [want(k) for k in range(100_000)]
    assert length == pytest.approx(blocks * 700e-9)
    assert busy == pytest.approx(blocks * 7 * 50e-9)
