"""Share of the time the engine held a queued or active request (the
harness's ``engine_busy`` spans) in which no operation ran on the chip.
Time with no request is left out, so a faster device does not read as more
idle at a fixed rate.  Moves ``itl_p99_ms``."""

UNIT = "%"


def read(run):
    if not run.counts.get("serve_requests"):
        return None
    busy, total = run.trace.busy_within(run.trace.spans("engine_busy"))
    if total <= 0:
        return None
    return 100.0 * (1.0 - busy / total)
