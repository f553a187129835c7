"""The harness finds every cell, mix, configuration and metric by name, a
new one is picked up from its file alone, and no run measures without a
TPU."""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import common

ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())


def test_benchmark_json_matches_files():
    assert SPEC["command"] == ["python3", "bench/run.py"]
    assert SPEC["paths"] == ["bench"]
    for w in SPEC["workloads"]:
        cell = common.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        common.system(cell["config_spec"])
        common.generator(cell["traffic_spec"])
    readers = common.metric_readers()
    for m in SPEC["per_layer"]:
        assert m["name"] in readers and readers[m["name"]].UNIT == m["unit"]
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert common.load_json("configs", c["name"])["reduced"] == c["reduced"]


@pytest.mark.parametrize("path", sorted((ROOT / "bench" / "cells").glob("*.json")),
                         ids=lambda p: p.stem)
def test_every_cell_names_an_existing_config_and_mix(path):
    cell = common.load_cell(path.stem)
    assert cell["chips"] in (1, 4)
    assert (ROOT / "bench" / "systems"
            / f"{cell['config_spec']['system']}.py").is_file()
    assert (ROOT / "bench" / "generators"
            / f"{cell['traffic_spec']['generator']}.py").is_file()


def test_new_cell_mix_and_metric_need_no_edit(tmp_path, monkeypatch):
    import shutil
    import bench.metrics
    bench_copy = tmp_path / "bench"
    for kind in ("configs", "traffic", "cells"):
        shutil.copytree(ROOT / "bench" / kind, bench_copy / kind)
    (bench_copy / "metrics").mkdir()
    (bench_copy / "traffic" / "ycsb-a-zipf99.json").write_text(json.dumps({
        "generator": "ycsb", "distribution": "zipfian", "zipf_theta": 0.99,
        "read_proportion": 0.5, "update_proportion": 0.5, "in_flight": 2}))
    (bench_copy / "cells" / "kv-ycsb-a-zipf99.json").write_text(json.dumps({
        "config": "msl-kv-268m", "traffic": "ycsb-a-zipf99", "chips": 1}))
    (bench_copy / "metrics" / "kv_new_share.py").write_text(
        'UNIT = "%"\n\ndef read(run):\n    return None\n')
    monkeypatch.setattr(common, "BENCH", bench_copy)
    monkeypatch.setattr(bench.metrics, "__path__",
                        [str(bench_copy / "metrics")])
    cell = common.load_cell("kv-ycsb-a-zipf99")
    assert cell["traffic_spec"]["update_proportion"] == 0.5
    assert common.generator(cell["traffic_spec"]).Stream
    assert "kv_new_share" in common.metric_readers()
    with pytest.raises(FileNotFoundError):
        common.load_cell("no-such-cell")


def test_run_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "kv-ycsb-b-zipf99",
         "--seed", str(2**33 + 1), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert "needs a TPU" in out.stderr
    assert not out.stdout.strip()
