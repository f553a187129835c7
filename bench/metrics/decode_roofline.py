"""The decode launch's share of its roofline: the least time the chip
needs for the window's decode launches, the larger of their model FLOPs
over the bf16 peak and the bytes they need over the HBM peak, divided by
their device time (the ``decode_step`` programs in the trace).

The bytes are every weight once per launch, each active row's live KV at
its own length and its new KV row (``bench.counts.decode_bytes``); at
these sizes the bytes bound it.  Moves ``itl_p99_ms``."""

UNIT = "%"
PROGRAM = r"jit_decode_step"


def read(run):
    c = run.counts
    if not c.get("decode_launches"):
        return None
    s = run.trace.seconds(PROGRAM, "modules")
    if s <= 0:
        return None
    least = max(c["decode_flops"] / run.peaks["bf16_flops"],
                c["decode_bytes"] / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / s
