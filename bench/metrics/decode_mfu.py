"""Model FLOP utilisation of the whole decode step: the model FLOPs of the
window's decode rows (every matmul weight, the LM head, and attention over
each row's live length; ``bench.counts.decode_flops``) over the device time
of the ``decode_step`` programs, as a share of the bf16 peak.  Moves
``itl_p99_ms``."""

UNIT = "%"
PROGRAM = r"jit_decode_step"


def read(run):
    c = run.counts
    if not c.get("decode_launches"):
        return None
    s = run.trace.seconds(PROGRAM, "modules")
    if s <= 0:
        return None
    return 100.0 * c["decode_flops"] / s / run.peaks["bf16_flops"]
