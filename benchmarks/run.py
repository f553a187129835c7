"""Benchmark suite: one module per paper figure + roofline + serving.

``PYTHONPATH=src python -m benchmarks.run [--force] [--quick]``

Results are cached under results/bench/ so re-runs are instant; --force
recomputes.  Output: human-readable report + ``name,us_per_call,derived``
CSV lines at the end.

``fig14`` and ``sharded`` are CPU count runs: each starts a child process
with ``JAX_PLATFORMS=cpu`` and forced host devices, which exist only on the
CPU.  On a TPU host the parent process keeps the chip and the children
never ask for it.
"""

from __future__ import annotations

import argparse
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--quick", action="store_true",
                    help="skip the slowest modules (fig07 python baselines)")
    ap.add_argument("--engine", choices=["rounds", "onepass"], default="rounds",
                    help="batched conflict scheme for fig08 and the prefix "
                         "bench (other figures keep their pinned engines)")
    args = ap.parse_args()

    from benchmarks import (fig06_invector_small, fig07_hit_ratio,
                            fig08_throughput, fig11_m_sweep,
                            fig12_hit_location, fig13_p8,
                            fig14_sharded_scaling, fig15_warmup,
                            prefix_cache_bench, roofline_table,
                            serve_bench, sharded_bench)

    modules = [
        ("fig06", fig06_invector_small),
        ("fig07", fig07_hit_ratio),
        ("fig08", fig08_throughput),
        ("fig11", fig11_m_sweep),
        ("fig12", fig12_hit_location),
        ("fig13", fig13_p8),
        ("fig14", fig14_sharded_scaling),
        ("fig15", fig15_warmup),
        ("prefix", prefix_cache_bench),
        ("sharded", sharded_bench),
        ("serve", serve_bench),
    ]
    if args.quick:
        modules = [m for m in modules
                   if m[0] not in ("fig07", "fig14", "sharded", "serve")]

    csv = ["name,us_per_call,derived"]
    for name, mod in modules:
        t0 = time.time()
        if name in ("fig08", "prefix"):
            res = mod.run(force=args.force, engine=args.engine)
        else:
            res = mod.run(force=args.force)
        print("\n".join(mod.report(res)))
        print(f"  ({name} wall: {time.time()-t0:.1f}s)\n")
        us, derived = _csv_scalars(name, res)
        csv.append(f"{name},{us},{derived}")

    print("\n".join(roofline_table.report("pod1")))
    print()
    try:
        print("\n".join(roofline_table.report("pod2")))
    except Exception:
        print("(multi-pod table unavailable)")

    print("\n" + "\n".join(csv))


def _csv_scalars(name, res):
    try:
        if name == "fig06":
            return res["keys20"]["invector"]["us_per_query"], \
                res["keys20"]["invector"]["hit_ratio"]
        if name == "fig07":
            return 0, res["zipfian"]["multistep"]["65536"]
        if name == "fig08":
            return res["262144"]["multistep_batched"]["us_per_query"], \
                res["262144"]["multistep_batched"]["qps"]
        if name == "fig11":
            return res["M2"]["us_per_query"], res["M2"]["hit_ratio"]
        if name == "fig12":
            return 0, res["zipfian"]["M2"]["vector_frac"][0]
        if name == "fig13":
            return res["p8_m2"]["us_per_query"], res["p8_m2"]["hit_ratio"]
        if name == "fig14":
            return 0, res["D8"]["hits"]
        if name == "fig15":
            return 0, res["multistep_garbage"]["1048576"]
        if name == "prefix":
            return 0, res["multistep_m2"]["prefill_saved_frac"]
        if name == "sharded":
            return 0, res["2x"]["shed_rate"]
        if name == "serve":
            return 0, res["inflight"]["launches_per_token"]
    except (KeyError, IndexError):
        pass
    return 0, 0


if __name__ == "__main__":
    main()
