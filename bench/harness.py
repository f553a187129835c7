"""One run of one cell: device check, the system's set-up and window, the
trace reduction, the per-layer readers and the result line."""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import shutil
import tempfile
import time

from bench import common, peaks as peaks_mod


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``value <= limit`` (rule "<=")
    or ``value >= limit`` (rule ">=")."""
    value: float
    limit: float
    rule: str = "<="

    def ok(self) -> bool:
        if self.rule == "<=":
            return self.value <= self.limit
        return self.value >= self.limit


@dataclasses.dataclass
class Outcome:
    """What a system returns from one run."""
    metrics: dict                 # end-to-end metric -> (value, unit)
    counts: dict                  # what the per-layer readers need
    checks: dict                  # name -> Check
    attempted: int
    failed: int
    device: dict                  # common.device_stamp, read before the check


class Context:
    """What a system gets: the cell, the seed and window, the devices, the
    clock, and ``traced()`` around whatever the trace should cover."""

    def __init__(self, cell, seed, seconds, trace, devices, clock, counter,
                 keep=None):
        self.cell = cell
        self.config = cell["config_spec"]
        self.traffic = cell["traffic_spec"]
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.devices = devices
        self.clock = clock
        self.counter = counter
        self.trace_dir = None
        self.setup_s = None
        self.window_compiles = None
        # a dict the system fills with what its comparison read, for the
        # control (bench/control.py); None in a benchmark run
        self.keep = keep

    def open_window(self):
        """Marks the end of set-up; returns the compile counts to diff."""
        self.setup_s = self.clock.now()
        return self.counter.snapshot()

    def close_window(self, at_open):
        c, h = self.counter.snapshot()
        self.window_compiles = (c - at_open[0], h - at_open[1])

    @contextlib.contextmanager
    def traced(self):
        if not self.trace:
            yield
            return
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        # device activity and the harness's own spans; no Python call tracing
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation("window"):
                yield
        finally:
            jax.profiler.stop_trace()


def parse_args(argv):
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    return ap.parse_args(argv)


def configure_jax():
    import jax
    # every program goes to the persistent cache, however quick to compile,
    # so that a warm run finds all of them
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return jax


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, clock,
             devices, keep=None) -> dict:
    """Drive one run of ``cell`` on ``devices``; returns the result line."""
    peaks = peaks_mod.lookup(devices[0].device_kind)
    counter = common.CompileCounter()
    ctx = Context(cell, seed, seconds, trace, devices, clock, counter, keep)
    outcome = common.system(cell["config_spec"]).run(ctx)
    common.log(f"[bench] set-up {ctx.setup_s:.3f} s; in the window "
               f"{ctx.window_compiles[0]} compiles and "
               f"{ctx.window_compiles[1]} persistent-cache loads")
    device = dict(outcome.device)
    if trace:
        from bench import trace as trace_mod
        t = time.perf_counter()
        summary = trace_mod.reduce_dir(ctx.trace_dir, len(devices))
        shutil.rmtree(ctx.trace_dir, ignore_errors=True)
        metrics = {}
        view = trace_mod.RunView(outcome.counts, summary, peaks, cell)
        for name, reader in common.metric_readers().items():
            got = reader.read(view)
            if got is not None:
                metrics[name] = {"value": got, "unit": reader.UNIT}
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        breakdown = summary.breakdown()
        common.log(f"[bench] trace reduced in {time.perf_counter() - t:.1f} s")
    else:
        metrics = {name: {"value": v, "unit": u}
                   for name, (v, u) in outcome.metrics.items()}
        breakdown = None
    correct = all(c.ok() for c in outcome.checks.values())
    line = {"correct": correct, "attempted": outcome.attempted,
            "failed": outcome.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {name: {"value": c.value, "limit": c.limit,
                             "rule": c.rule}
                      for name, c in outcome.checks.items()}
    return line


def main(argv, t0) -> int:
    args = parse_args(argv)
    clock = common.Clock(t0)
    cell = common.load_cell(args.workload)
    jax = configure_jax()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        common.log(f"bench: needs a TPU, JAX found {devices[0].platform}")
        return 3
    if len(devices) < cell["chips"]:
        common.log(f"bench: cell {args.workload} needs {cell['chips']} "
                   f"chips, JAX found {len(devices)}")
        return 3
    line = run_cell(cell, args.seed, args.seconds, bool(args.trace), clock,
                    devices[:cell["chips"]])
    for name, c in line["checks"].items():
        common.log(f"check {name}: {c['value']} (limit {c['rule']} "
                   f"{c['limit']})")
    print(json.dumps(line), flush=True)
    return 0
