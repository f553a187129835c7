"""The cache cells' batch ring on the CPU: the window consumes exactly the
stream's batches, in order, and the ring's child process and memory are
gone after a run, whether it ends normally or raises in the window."""

import numpy as np
import pytest

from bench import kv_ring
from bench.generators import ycsb
from bench.systems import kv
from bench.tests import _tiny

CELL = "kv-ycsb-b-zipf99"
SEED = 2**33 + 17
OP_CODES = (0, 2)


def _want(stream, i):
    keys, ops = stream.batch(i)
    return (keys, ycsb.record_values(keys),
            np.asarray(OP_CODES, np.int32)[ops.astype(np.int64)])


def test_ring_gives_the_stream_in_order():
    traffic = _tiny.cell(CELL)["traffic_spec"]
    stream = ycsb.Stream(traffic, _tiny.KV["key_space"], 256, SEED)
    with kv_ring.BatchRing(traffic, _tiny.KV["key_space"], 256, SEED,
                           OP_CODES, slots=4) as ring:
        ring.wait_full()
        for i in range(41):
            got = ring.take(i)
            for have, want in zip(got, _want(stream, i)):
                assert have.dtype == np.int32 and have.shape == want.shape
                assert have.tobytes() == want.astype(np.int32).tobytes(), i
            ring.release()
    assert ring.proc.returncode == 0


@pytest.fixture
def rings(monkeypatch):
    """Every ring a run makes, to look for what it left behind."""
    made = []
    real = kv_ring.BatchRing.__init__

    def init(self, *a, **k):
        real(self, *a, **k)
        made.append(self)
    monkeypatch.setattr(kv_ring.BatchRing, "__init__", init)
    return made


def _nothing_left(rings):
    """The child ended on its own when the ring closed, was waited for,
    and the ring's memory is unmapped."""
    assert len(rings) == 1
    assert rings[0].proc.returncode == 0
    assert rings[0].buf.closed


def test_window_consumes_the_stream(rings):
    keep = {}
    c = _tiny.cell(CELL)
    line = _tiny.run(c, seed=SEED, keep=keep)
    assert line["correct"], line["checks"]
    batch = _tiny.KV["batch_per_chip"]
    n = line["attempted"] // batch
    assert n > 10 and keep["keys"].size == n * batch
    stream = ycsb.Stream(c["traffic_spec"], _tiny.KV["key_space"], batch,
                         SEED)
    keys, ops = zip(*(stream.batch(i) for i in range(n)))
    assert keep["keys"].tobytes() == np.concatenate(keys).tobytes()
    assert keep["ops"].tobytes() == np.concatenate(ops).tobytes()
    _nothing_left(rings)


def _raise_in_window(monkeypatch):
    from repro.core import MultiStepLRUCache
    real = MultiStepLRUCache.access
    calls = []

    def access(self, keys, vals=None, ops=None, *a, **k):
        if ops is not None:
            calls.append(1)
            if len(calls) > 5:                # past the warm-up's two
                raise RuntimeError("planted fault")
        return real(self, keys, vals, ops, *a, **k)
    monkeypatch.setattr(MultiStepLRUCache, "access", access)


@pytest.mark.parametrize("how", ["program", "buffers_full"])
def test_nothing_left_after_a_run_that_raises(monkeypatch, rings, how):
    if how == "program":
        _raise_in_window(monkeypatch)
        match = "planted fault"
    else:
        # buffers for 1,000 ops/s: a window of 1 s holds 4 + 2 batches
        monkeypatch.setattr(kv, "MAX_OPS_PER_S_PER_CHIP", 1000)
        match = "the most its buffers hold"
    with pytest.raises(RuntimeError, match=match):
        _tiny.run(_tiny.cell(CELL), seed=SEED)
    _nothing_left(rings)
