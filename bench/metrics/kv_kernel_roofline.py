"""The cache kernel's share of its roofline: the least time the chip needs
for a batch's work, divided by the kernel's device time per batch.

The work is counted from the batch alone, the same for any layout or
engine: one row (lanes x planes x 4 B) read and one written per distinct
set, and each query's key, value and op in and its result out
(``bench.counts.kv_batch_bytes``), over the HBM peak.  The kernel is the
one-pass Pallas kernel of ``kernels/msl_cache.py``.  Moves
``kv_ops_per_s``."""

from bench.counts import kv_batch_bytes

UNIT = "%"
KERNEL = r"msl_onepass_kernel_call"


def read(run):
    c = run.counts
    n = c.get("kv_batches")
    if not n:
        return None
    s = run.trace.seconds(KERNEL, "ops")
    if s <= 0:
        return None
    need = kv_batch_bytes(c["kv_distinct_sets_mean"], c["kv_batch_queries"],
                          c["kv_row_bytes"], c["kv_query_bytes"],
                          c["kv_result_bytes"])
    least = need / (run.peaks["hbm_bytes_per_s"] * c.get("chips", 1))
    return 100.0 * least / (s / n)
