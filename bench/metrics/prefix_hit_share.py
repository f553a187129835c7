"""Share of the window's prompt tokens whose prefill the prefix cache
skipped: sum of ``Request.prefill_skipped`` over sum of prompt lengths, of
the requests due in the window.  A count, not a time.  Moves
``ttft_p90_ms``."""

UNIT = "%"


def read(run):
    c = run.counts
    if not c.get("serve_prompt_tokens"):
        return None
    return 100.0 * c["serve_prefill_skipped"] / c["serve_prompt_tokens"]
