"""Device time of the sharded route's all_to_all exchanges per call,
averaged over the chips.  Only a sharded cell has them.  Moves
``kv_ops_per_s``."""

UNIT = "ms"
OPS = r"all-to-all"


def read(run):
    n = run.counts.get("kv_batches")
    if not n or run.counts.get("chips", 1) < 2:
        return None
    s = run.trace.seconds(OPS, "ops")
    return s / n * 1e3 if s > 0 else None
