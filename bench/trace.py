"""From a profiler trace of the window to device time, idle time and the
breakdown, with the harness's host spans on the same clock.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``: their "XLA Ops" line holds one event per
operation run, their "XLA Modules" line one per program run.  The host
plane's Python thread holds the spans the harness writes with
``jax.profiler.TraceAnnotation``; the outermost one, ``window``, bounds
the reduction.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import heapq
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


@dataclasses.dataclass
class Summary:
    window: tuple               # (start, end) ns
    ops: dict                   # device -> [(name, start, end)]
    modules: dict               # device -> [(name, start, end)]
    host: list                  # [(name, start, end)]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over the chips."""
        if not self.ops:
            return 0.0
        return sum(union_ns([(s, e) for _, s, e in evs])
                   for evs in self.ops.values()) * 1e-9 / len(self.ops)

    def seconds(self, pattern: str, line: str = "ops") -> float:
        """Device seconds of events whose name matches ``pattern``, per chip
        (overlaps on one chip counted once)."""
        rx = re.compile(pattern)
        src = self.ops if line == "ops" else self.modules
        if not src:
            return 0.0
        tot = 0
        for evs in src.values():
            tot += union_ns([(s, e) for n, s, e in evs if rx.search(n)])
        return tot * 1e-9 / len(src)

    def count(self, pattern: str, line: str = "modules") -> float:
        """Events whose name matches, per chip."""
        rx = re.compile(pattern)
        src = self.ops if line == "ops" else self.modules
        if not src:
            return 0.0
        return sum(sum(1 for n, _, _ in evs if rx.search(n))
                   for evs in src.values()) / len(src)

    def spans(self, name: str) -> list:
        return [(s, e) for n, s, e in self.host if n == name]

    def busy_within(self, intervals) -> tuple[float, float]:
        """(device busy s, length s) inside ``intervals``, per chip."""
        ivs = merge(intervals)
        total = sum(e - s for s, e in ivs)
        if not self.ops or not total:
            return 0.0, total * 1e-9
        busy = 0
        for evs in self.ops.values():
            busy += union_ns(clip([(s, e) for _, s, e in evs], ivs))
        return busy * 1e-9 / len(self.ops), total * 1e-9

    def idle_gaps(self) -> list:
        """[(host span, seconds)] of every gap between busy intervals on
        the first chip, inside the window."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        busy = merge([(s, e) for _, s, e in self.ops[dev]])
        gaps, t = [], self.window[0]
        for s, e in busy + [(self.window[1], self.window[1])]:
            if s > t:
                gaps.append(((t + s) / 2, (s - t) * 1e-9))
            t = max(t, e)
        names = self.hosts_at([m for m, _ in gaps])
        return [(n, g) for n, (_, g) in zip(names, gaps)]

    def hosts_at(self, times) -> list:
        """The innermost (shortest) host span open at each of ``times``,
        the first in ``host`` among equals, and ``WINDOW`` where none is.
        One sweep in time order: O((times + spans) log spans)."""
        spans = sorted((s, e - s, k, n, e)
                       for k, (n, s, e) in enumerate(self.host) if n != WINDOW)
        order = sorted(range(len(times)), key=lambda q: times[q])
        out = [WINDOW] * len(times)
        open_, i = [], 0
        for q in order:
            t = times[q]
            while i < len(spans) and spans[i][0] <= t:
                _, size, k, n, e = spans[i]
                heapq.heappush(open_, (size, k, n, e))
                i += 1
            # a span ended by t is ended for every later query
            while open_ and open_[0][3] <= t:
                heapq.heappop(open_)
            if open_:
                out[q] = open_[0][2]
        return out

    def breakdown(self) -> dict:
        per_op: dict = {}
        for evs in self.ops.values():
            for n, s, e in evs:
                n = op_label(n)
                per_op[n] = per_op.get(n, 0) + (e - s)
        k = max(len(self.ops), 1)
        ops = sorted(((n, v * 1e-9 / k) for n, v in per_op.items()),
                     key=lambda x: -x[1])[:10]
        gaps = sorted(self.idle_gaps(), key=lambda x: -x[1])[:10]
        return {"device_ops": [list(x) for x in ops],
                "idle_gaps": [list(x) for x in gaps]}


def op_label(text: str) -> str:
    """``name opcode`` of an HLO instruction's text (the device op events
    carry the whole instruction), e.g. ``copy.39 copy``."""
    name, sep, rest = text.partition(" = ")
    if not sep:
        return text[:80]
    m = re.search(r"[\]\}\)] ([a-z][\w\-]*)\(", rest)
    return f"{name.lstrip('%')} {m.group(1)}" if m else name.lstrip("%")


def merge(intervals) -> list:
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        elif e > s:
            out.append((s, e))
    return out


def union_ns(intervals) -> float:
    return sum(e - s for s, e in merge(intervals))


def clip(intervals, within) -> list:
    """Parts of ``intervals`` inside the merged, sorted ``within``, in the
    order of ``intervals`` and then of ``within``."""
    ends = [b for _, b in within]
    out = []
    for s, e in intervals:
        j = bisect.bisect_right(ends, s)        # the first that ends after s
        while j < len(within) and within[j][0] < e:
            lo, hi = max(s, within[j][0]), min(e, within[j][1])
            if hi > lo:
                out.append((lo, hi))
            j += 1
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def reduce_file(path: str) -> Summary:
    from jax._src.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, modules, host = {}, {}, []
    for plane in data.planes:
        m = re.fullmatch(r"/device:TPU:(\d+)", plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops[dev] = list(_events(line))
                elif line.name == MODULES_LINE:
                    modules[dev] = list(_events(line))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n in HOST_SPANS:
                        host.append((n, s, e))
    wins = [(s, e) for n, s, e in host if n == WINDOW]
    if not wins:
        raise ValueError(f"no '{WINDOW}' span in {path}")
    window = (min(s for s, _ in wins), max(e for _, e in wins))

    def inside(evs):
        return [(n, max(s, window[0]), min(e, window[1])) for n, s, e in evs
                if e > window[0] and s < window[1]]

    return Summary(window, {d: inside(v) for d, v in ops.items()},
                   {d: inside(v) for d, v in modules.items()}, host)


def reduce_dir(trace_dir: str, chips: int) -> Summary:
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(files) != 1:
        raise ValueError(f"expected one trace under {trace_dir}, found "
                         f"{len(files)}")
    summary = reduce_file(files[0])
    if len(summary.ops) < chips:
        raise ValueError(f"trace has {len(summary.ops)} devices with ops, "
                         f"the cell uses {chips}")
    return summary


# host spans the harness, the systems and the program (``msl.access``, one
# cache engine call) write
HOST_SPANS = {WINDOW, "dispatch", "fetch", "gen_wait", "submit", "step",
              "sleep", "engine_busy", "msl.access"}


@dataclasses.dataclass
class RunView:
    """What a per-layer reader sees: the system's counts, the trace summary,
    the peaks of the chip and the cell."""
    counts: dict
    trace: Summary
    peaks: dict
    cell: dict
