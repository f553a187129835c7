#!/usr/bin/env python3
"""The control of a cell's comparison, beside the program's own readings.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds <s>

For each seed it makes one run of the cell, as ``bench/run.py`` does, and
then reads the same comparison with the control in the program's place:

* a cache cell: the plain reference with its recency guarantee broken
  (a hit leaves the set as it was), replayed over the same sample; the
  numbers are the answers and final rows that differ from the reference;
* a serving cell: the float32 reference with its weights in fp8 (e4m3, one
  scale per matrix), the precision below the configuration's bf16; the
  number is the widest gap, in the reference's logits, of the token the
  control puts first at each position the program's tokens were compared.

The benchmark's own runs never run the control.  The limits in
``BENCHMARK.json``'s cells sit between the program's readings and these.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu's own logs stay in the checkout too
os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import common, harness  # noqa: E402
from bench.generators import ycsb  # noqa: E402


def kv_control(keep: dict) -> dict:
    from bench.reference import kvcache as ref
    vals = ycsb.record_values(keep["keys"])
    answers, final = ref.answers_of(keep["rows_before"], keep["p"],
                                    keep["keys"], vals, keep["ops"],
                                    keep["sets"], promote=False)
    fields = {f: answers[f] for f in keep["answers"]}
    bad, checked, _ = ref.replay(keep["rows_before"], keep["p"], keep["keys"],
                                 vals, keep["ops"], keep["sets"], fields)
    return {"kv_answers_wrong": bad, "kv_rows_wrong":
            ref.rows_differ(keep["final"], final), "checked": checked}


def serve_control(keep: dict) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from bench.reference import phi3
    cfg = keep["cfg"]
    fn = jax.jit(lambda w, t, p, s: phi3.gaps(
        w, t, p, s, n_heads=cfg.n_heads, eps=cfg.norm_eps,
        theta=cfg.rope_theta, control=True))
    g = np.asarray(fn(keep["params"], jnp.asarray(keep["tokens"]),
                      jnp.asarray(keep["positions"]),
                      jnp.asarray(keep["served"])))
    return {"served_logit_gap": float(g.max()),
            "checked": int((keep["positions"] >= 0).sum())}


CONTROLS = {"kv": kv_control, "serve": serve_control}


def control_reading(cell: dict, seed: int, seconds: float, clock,
                    devices) -> dict:
    keep: dict = {}
    line = harness.run_cell(cell, seed, seconds, False, clock, devices, keep)
    ctl = CONTROLS[cell["config_spec"]["system"]](keep)
    keep.clear()
    return {"seed": seed, "program": {k: v["value"] for k, v in
                                      line["checks"].items()},
            "program_correct": line["correct"], "control": ctl}


def main(argv) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = common.load_cell(args.workload)
    jax = harness.configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        common.log("control: needs a TPU with the cell's chips")
        return 3
    out = []
    for s in args.seeds.split(","):
        r = control_reading(cell, int(s), args.seconds, common.Clock(T0),
                            devices[:cell["chips"]])
        print(json.dumps(r), flush=True)
        out.append(r)
    print(json.dumps({"workload": args.workload, "readings": out}))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
