"""The serving cell's comparison on the CPU at the smoke size: a sound run
is correct, each fault the cell can have is not, and the fp8 control reads
wider gaps than the program."""

import pytest

from bench.tests import _tiny

CELL = "phi3-chat-shared"
# the smoke model's logits are ~20x narrower than the published widths'
LIMIT = 0.02


def _cell():
    c = _tiny.cell(CELL)
    c["max_logit_gap"] = LIMIT
    return c


def test_sound_run_is_correct_and_control_reads_wider():
    keep = {}
    line = _tiny.run(_cell(), seconds=2.0, keep=keep)
    assert line["correct"], line["checks"]
    assert set(line["metrics"]) == {"ttft_p90_ms", "itl_p99_ms", "setup_s"}
    assert line["attempted"] == 12 and line["failed"] == 0
    from bench.control import serve_control
    ctl = serve_control(keep)
    prog = line["checks"]["served_logit_gap"]["value"]
    assert ctl["served_logit_gap"] > 3 * prog


def _broken(kind):
    from repro.serving.engine import ServeEngine
    if kind == "state":
        def merge(self, new_cache, accept):     # decode KV never kept
            return None
        return "_merge_cache", merge
    real = ServeEngine._emit

    def emit(self, req, tok):
        if kind == "answer" and len(req.out_tokens) == 2:
            tok = (tok + 1) % self.cfg.vocab_size
        if kind == "half" and req.slot % 2 == 1 and req.out_tokens:
            tok = req.out_tokens[-1]            # odd rows left out
        return real(self, req, tok)
    return "_emit", emit


@pytest.mark.parametrize("kind", ["state", "half", "answer"])
def test_fault_is_caught(monkeypatch, kind):
    from repro.serving.engine import ServeEngine
    name, fn = _broken(kind)
    monkeypatch.setattr(ServeEngine, name, fn)
    line = _tiny.run(_cell(), seconds=2.0)
    assert not line["correct"], (kind, line["checks"])
