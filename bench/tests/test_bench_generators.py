"""The traffic generators: YCSB's scrambled zipfian and op mixes, and the
chat mix, are deterministic per seed and keep their stated shares."""

import numpy as np
import pytest

from bench.generators import chat, ycsb

ZIPF = {"generator": "ycsb", "distribution": "zipfian", "zipf_theta": 0.99,
        "read_proportion": 0.95, "update_proportion": 0.05, "in_flight": 2}


def _fnv_python(v: int) -> int:
    """YCSB's Utils.fnvhash64 on Python integers."""
    h = 0xCBF29CE484222325
    for _ in range(8):
        h ^= v & 0xFF
        h = (h * 1099511628211) % 2**64
        v >>= 8
    if h >= 2**63:
        h -= 2**64
    return abs(h)


def test_fnvhash64_matches_ycsb():
    vals = [0, 1, 2, 255, 256, 12345, 2**31 - 1, 2**32 + 7, 10**10]
    got = ycsb.fnvhash64(np.array(vals, np.int64))
    assert got.tolist() == [_fnv_python(v) for v in vals]


@pytest.mark.parametrize("key_space", [2**17, 2**29, 2**31 - 1])
def test_device_scrambling_matches_host(key_space):
    import jax.numpy as jnp
    ranks = np.concatenate([np.arange(4096), [2**20, 2**28 - 1, 2**30,
                                              2**31 - 1]]).astype(np.int64)
    dev = np.asarray(ycsb.popular_keys(jnp.asarray(ranks, jnp.uint32),
                                       key_space))
    assert dev.tolist() == ycsb.popular_keys_host(ranks, key_space).tolist()
    keys = np.arange(1000, dtype=np.int32)
    assert (np.asarray(ycsb.record_values_device(jnp.asarray(keys)))
            == ycsb.record_values(keys)).all()


def test_zipf_rank0_frequency_is_ycsb_constant():
    u = np.random.default_rng(0).random(400_000)
    share = float((ycsb.zipf_ranks(u) == 0).mean())
    assert share == pytest.approx(1 / ycsb.ZIPF_ZETAN, abs=0.0015)


def test_ycsb_stream_deterministic_per_seed_and_mix():
    a = ycsb.Stream(ZIPF, 2**29, 8192, seed=2**40 + 3)
    b = ycsb.Stream(ZIPF, 2**29, 8192, seed=2**40 + 3)
    c = ycsb.Stream(ZIPF, 2**29, 8192, seed=2**40 + 4)
    ka, oa = a.batch(5)
    kb, ob = b.batch(5)
    assert (ka == kb).all() and (oa == ob).all()
    assert not (ka == c.batch(5)[0]).all()
    assert ka.min() >= 0 and ka.max() < 2**29
    ops = np.concatenate([a.batch(i)[1] for i in range(20)])
    assert ops.mean() == pytest.approx(0.05, abs=0.005)


def test_uniform_read_only_mix():
    mix = {"distribution": "uniform", "read_proportion": 1.0,
           "update_proportion": 0.0}
    keys, ops = ycsb.Stream(mix, 1000, 50_000, seed=1).batch(0)
    assert (ops == ycsb.READ).all()
    assert keys.min() >= 0 and keys.max() < 1000
    assert np.bincount(keys, minlength=1000).max() < 100


CHAT = {"generator": "chat", "system_prompts": 8, "system_prompt_tokens": 256,
        "prompt_popularity_alpha": 1.0,
        "user_tokens": {"median": 48, "sigma": 0.8, "min": 8, "max": 128},
        "output_tokens": {"median": 48, "sigma": 0.8, "min": 8, "max": 128},
        "arrivals": "poisson", "sampling": "greedy", "shape_seed": 7}


def test_chat_schedule_same_work_in_another_order():
    a = chat.schedule(CHAT, 4.0, 30.0, seed=11, vocab=32064)
    b = chat.schedule(CHAT, 4.0, 30.0, seed=11, vocab=32064)
    c = chat.schedule(CHAT, 4.0, 30.0, seed=2**33 + 5, vocab=32064)
    assert len(a) == len(c) == 120
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert all((x.prompt == y.prompt).all() for x, y in zip(a, b))
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new_tokens for r in a) == sorted(
        r.max_new_tokens for r in c)
    assert [r.due_s for r in a] != [r.due_s for r in c]
    assert max(r.due_s for r in a) < 30.0
    assert np.all(np.diff([r.due_s for r in a]) >= 0)


def test_chat_lengths_clipped_and_templates_shared():
    reqs = chat.schedule(CHAT, 10.0, 60.0, seed=3, vocab=32064)
    temps = chat.templates(CHAT, 3, 32064)
    for r in reqs:
        assert 8 <= len(r.prompt) - 256 <= 128
        assert 8 <= r.max_new_tokens <= 128
        assert (r.prompt[:256] == temps[r.template]).all()
        assert len(r.prompt) + r.max_new_tokens <= 512
    picks = np.bincount([r.template for r in reqs], minlength=8)
    assert picks[0] > picks[7]


def test_chat_warmup_covers_every_length_and_batch():
    groups = chat.warmup(CHAT, 5, 32064, slots=8, buckets=[8, 16, 32, 64, 128])
    lens = {len(r.prompt) - 256 for g in groups for r in g}
    assert lens == set(range(8, 129))
    assert {len(g) for g in groups} >= {1, 2, 4, 8}
    assert all(len(g) <= 8 for g in groups)
    seeded = {r.template for g in groups[:8] for r in g}
    assert seeded == set(range(8))
