"""Device time of the prefill programs per prompt token they computed:
the engine's jitted continuation-prefill launches (``jax.jit`` of a lambda,
so their programs are named ``jit__lambda``) over the sum of
``Request.prefill_computed`` in the window.  Moves ``ttft_p90_ms``."""

UNIT = "us/token"
PROGRAM = r"jit__lambda"


def read(run):
    n = run.counts.get("serve_prefill_computed")
    if not n:
        return None
    s = run.trace.seconds(PROGRAM, "modules")
    return s / n * 1e6 if s > 0 else None
