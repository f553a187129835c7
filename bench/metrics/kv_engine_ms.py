"""Device time of the cache engine's program per batch: the one-pass engine
(sort, gather, kernel, scatter and the table it writes) as one program,
``run_ops`` of ``make_batched_engine`` on one chip, the ``shard_map``
wrapper of ``make_sharded_engine`` on several.  Moves ``kv_ops_per_s``."""

UNIT = "ms"
PROGRAM = r"jit_(run_ops|wrapped)"


def read(run):
    n = run.counts.get("kv_batches")
    if not n:
        return None
    s = run.trace.seconds(PROGRAM, "modules")
    return s / n * 1e3 if s > 0 else None
