#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics read from a profiler trace of the window.  The run needs
a TPU with as many chips as the cell asks for, and exits non-zero without
printing a result where it finds fewer.  JAX's persistent compilation cache
lives in ``.jax_cache`` at the root of the checkout.
"""

import time

T0 = time.perf_counter()

import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
# before JAX is imported: the cache directory is part of every entry's key,
# so it is fixed, and inside the checkout
os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
# libtpu's own logs stay in the checkout too
os.environ["TPU_LOG_DIR"] = str(ROOT / ".tpu_logs")
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], T0))
