"""What every cell shares: finding files by name, seeds, the device stamp,
compile counting and the result line.

Each configuration, traffic mix, cell and per-layer metric lives in a file
of its own, found by the name ``BENCHMARK.json`` gives it:

    bench/configs/<config>.json     sizes, guarantees, ``system`` to drive it
    bench/traffic/<traffic>.json    parameters and the ``generator`` to read them
    bench/cells/<cell>.json         config, traffic, chips and the cell's own knobs
    bench/systems/<system>.py       drives one kind of system through one run
    bench/generators/<name>.py      turns a traffic file into requests
    bench/metrics/<metric>.py       reads one per-layer metric from a run
"""

from __future__ import annotations

import importlib
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def load_json(kind: str, name: str) -> dict:
    """``bench/<kind>/<name>.json``; a missing file is an error naming it."""
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """The cell with its configuration and traffic mix resolved by name."""
    cell = load_json("cells", name)
    cell["name"] = name
    cell["config_spec"] = load_json("configs", cell["config"])
    cell["traffic_spec"] = load_json("traffic", cell["traffic"])
    return cell


def generator(traffic: dict):
    return importlib.import_module(f"bench.generators.{traffic['generator']}")


def system(config: dict):
    return importlib.import_module(f"bench.systems.{config['system']}")


def metric_readers() -> dict:
    """Every per-layer metric reader under ``bench/metrics``, by name."""
    out = {}
    for path in sorted((BENCH / "metrics").glob("*.py")):
        if path.stem.startswith("_"):
            continue
        out[path.stem] = importlib.import_module(f"bench.metrics.{path.stem}")
    return out


def seed_words(seed: int) -> tuple[int, int]:
    """A seed of any size as two words below 2**31 (JAX's PRNG takes those)."""
    if seed < 0:
        raise ValueError(f"--seed must be a whole number >= 0, got {seed}")
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def jax_key(seed: int, stream: int = 0):
    import jax
    lo, hi = seed_words(seed)
    key = jax.random.fold_in(jax.random.PRNGKey(stream), lo)
    return jax.random.fold_in(key, hi)


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (no interpolation), q in (0, 100]."""
    import numpy as np
    v = np.sort(np.asarray(values, np.float64))
    if v.size == 0:
        raise ValueError("percentile of no samples")
    rank = int(np.ceil(q / 100.0 * v.size))
    return float(v[max(rank, 1) - 1])


class CompileCounter:
    """Counts XLA compiles and persistent-cache loads, so a run can say how
    many happened inside its measured window."""

    def __init__(self):
        import jax
        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits


def device_stamp(devices) -> dict:
    """Platform, kind and count as JAX reports them, and the peak bytes in
    use on the fullest of ``devices``."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class Clock:
    """Seconds since the process started (taken by ``bench/run.py``)."""

    def __init__(self, t0: float):
        self.t0 = t0

    def now(self) -> float:
        return time.perf_counter() - self.t0
