"""The cache cells' comparison on the CPU at a tiny size: a sound run is
correct, the control and each fault the cell can have are not."""

import numpy as np
import pytest

from bench.tests import _tiny

CELL = "kv-ycsb-b-zipf99"


def test_sound_run_is_correct():
    keep = {}
    line = _tiny.run(_tiny.cell(CELL), keep=keep)
    assert line["correct"], line["checks"]
    assert line["checks"]["kv_answers_checked"]["value"] >= 1000
    assert set(line["metrics"]) == {"kv_ops_per_s", "kv_p99_ms", "setup_s"}
    assert line["device"]["count"] == 1
    assert list(line)[-1] == "checks"
    from bench.control import kv_control
    ctl = kv_control(keep)
    assert ctl["kv_answers_wrong"] > 0 and ctl["kv_rows_wrong"] > 0


def test_uniform_cell_is_correct():
    assert _tiny.run(_tiny.cell("kv-ycsb-c-uniform"))["correct"]


def _broken(kind):
    from repro.core import MultiStepLRUCache
    real = MultiStepLRUCache.access

    def access(self, keys, vals=None, ops=None, *a, **k):
        if ops is None:                       # set-up's fill stays sound
            return real(self, keys, vals, ops, *a, **k)
        if kind == "state":
            before = self.table
            res = real(self, keys, vals, ops, *a, **k)
            self.table = before
            return res
        if kind == "half":
            h = keys.shape[0] // 2
            res = real(self, keys[:h], vals[:h], ops[:h], *a, **k)
            return type(res)(*[np.concatenate([np.asarray(x),
                                               np.zeros_like(np.asarray(x))])
                               for x in res])
        res = real(self, keys, vals, ops, *a, **k)
        hit = np.asarray(res.hit).copy()
        hit[0] = ~hit[0]
        return res._replace(hit=hit)
    return access


@pytest.mark.parametrize("kind", ["state", "half", "answer"])
def test_fault_is_caught(monkeypatch, kind):
    from repro.core import MultiStepLRUCache
    monkeypatch.setattr(MultiStepLRUCache, "access", _broken(kind))
    line = _tiny.run(_tiny.cell(CELL))
    assert not line["correct"], (kind, line["checks"])


_SHARDED = r"""
import json, os, sys, time
sys.path[:0] = [{root!r}, {src!r}]
import jax
from bench import common, harness, peaks
from bench.tests import _tiny
peaks.PEAKS.setdefault(jax.devices()[0].device_kind, peaks.PEAKS["TPU v5 lite"])
c = common.load_cell("kv4-ycsb-b-zipf99")
c["config_spec"].update(_tiny.KV, num_sets=4096, fill_ranks=16384)
if sys.argv[1] == "no_exchange":
    jax.lax.all_to_all = lambda x, *a, **k: x
line = harness.run_cell(c, 2**32 + 9, 1.0, False,
                        common.Clock(time.perf_counter()), jax.devices()[:4])
print(json.dumps(line["correct"]))
"""


@pytest.mark.parametrize("mode,want", [("sound", True),
                                       ("no_exchange", False)])
def test_sharded_cell_and_exchange_fault(mode, want):
    import json
    import os
    import subprocess
    import sys
    from pathlib import Path
    root = Path(__file__).resolve().parents[2]
    code = _SHARDED.format(root=str(root), src=str(root / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", code, mode], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) is want
