"""Share of the traced window in which no operation ran on the chip, in a
cache cell (averaged over the cell's chips).  Moves ``kv_ops_per_s``."""

UNIT = "%"


def read(run):
    if not run.counts.get("kv_batches") or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)
