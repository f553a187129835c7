"""Fig. 14 analogue: scaling of the SHARDED cache engine with device count.

The paper scales across cores with per-set locks; our analogue shards sets
across devices with all_to_all routing.  Fake host devices share one CPU
core here, so wall-clock doesn't scale — instead we verify the *structure*:
per-device query load and table shard scale 1/D, total hits stay exact, and
the collective schedule grows as expected.  Runs in subprocesses because
the XLA device count is locked per process.
"""

from __future__ import annotations

import json
import subprocess
import sys

from benchmarks.common import cached

_CHILD = r"""
import os, sys, json, time
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
os.environ["JAX_PLATFORMS"] = "cpu"  # forced host devices exist only on the CPU
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from repro.core import MSLRUConfig, init_table
from repro.core.sharded import make_sharded_engine, shard_table
from repro.data.ycsb import zipfian
from repro.launch.mesh import make_mesh_compat

D = %d
mesh = make_mesh_compat((D,), ("cache",))
cfg = MSLRUConfig(num_sets=16384, m=2, p=4, value_planes=0)
eng = make_sharded_engine(cfg, mesh, cap=8192 // D + 64)
tbl = shard_table(init_table(cfg), mesh)
trace = zipfian(1_000_000, 600_000, alpha=0.99, seed=21)
B = 8192
qv = jnp.zeros((B, 0), jnp.int32)
tbl, h, _, s = eng(tbl, jnp.asarray(trace[:B, None]), qv)  # compile
hits = served = 0
t0 = time.time()
for i in range(B, len(trace) - B, B):
    tbl, h, _, s = eng(tbl, jnp.asarray(trace[i:i+B, None]), qv)
    hits += int(h.sum()); served += int(s.sum())
dt = time.time() - t0
n = (len(trace) - 2 * B) // B * B
print(json.dumps({"devices": D, "hits": hits, "served": served, "n": n,
                  "qps": n / dt, "overflow_frac": 1 - served / n}))
"""


def run(force: bool = False):
    def compute():
        out = {}
        for d in (1, 2, 4, 8):
            res = subprocess.run(
                [sys.executable, "-c", _CHILD % (d, d)],
                capture_output=True, text=True, cwd=str(__import__("pathlib").Path(__file__).resolve().parent.parent))
            line = res.stdout.strip().splitlines()[-1]
            out[f"D{d}"] = json.loads(line)
        return out

    return cached("fig14_sharded_scaling", compute, force)


def report(res: dict) -> list[str]:
    lines = ["fig14: sharded-engine scaling (fake devices share 1 core; "
             "hit totals must be device-count-invariant)"]
    for k, r in res.items():
        lines.append(f"  {k}: hits={r['hits']} served={r['served']}/{r['n']} "
                     f"overflow={r['overflow_frac']:.2%} qps={r['qps']:.0f}")
    return lines


if __name__ == "__main__":
    print("\n".join(report(run())))
