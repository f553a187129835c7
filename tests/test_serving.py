"""Serving stack: prefix cache semantics, paged pool, engine equivalence."""

import numpy as np
import jax
import pytest

from repro.configs import get_config
from repro.models.model import make_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagedKVPool
from repro.serving.prefix_cache import PrefixCache, chunk_chain_hashes


def test_chain_hashes_prefix_property():
    rng = np.random.default_rng(0)
    a = rng.integers(1, 1000, 64).astype(np.int32)
    b = rng.integers(1, 1000, 64).astype(np.int32)
    h_ab = chunk_chain_hashes(np.concatenate([a, b]), 32)
    h_a = chunk_chain_hashes(a, 32)
    assert h_ab[:2] == h_a                 # shared prefix -> shared hashes
    c = b.copy()
    c[0] += 1
    h_ac = chunk_chain_hashes(np.concatenate([a, c]), 32)
    assert h_ab[:2] == h_ac[:2] and h_ab[2] != h_ac[2]


def test_pool_alloc_refcount():
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    pool = PagedKVPool(cfg, n_pages=4, page_tokens=8)
    pages = [pool.alloc() for _ in range(4)]
    assert pool.alloc() is None
    pool.pin(pages[0])
    pool.release(pages[0])       # still pinned -> deferred
    assert pool.free_pages == 0
    pool.unpin(pages[0])         # last reader gone -> really freed
    assert pool.free_pages == 1
    # an unpin beyond the pin count used to drive the refcount negative and
    # strand the page (neither free nor referenced); it must now fail loud
    with pytest.raises(AssertionError, match="unbalanced unpin"):
        pool.unpin(pages[0])


def test_pool_unpin_leak_guard():
    """A page whose refcount reaches 0 by unpin WITHOUT a deferred release
    must not silently leak: the pool either frees it (deferred) or raises
    (unbalanced unpin consumed the table's own reference)."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    pool = PagedKVPool(cfg, n_pages=2, page_tokens=8)
    pg = pool.alloc()            # table holds rc=1
    pool.pin(pg)                 # a reader
    pool.unpin(pg)               # balanced: rc back to the table's 1
    assert pool.free_pages == 1 and pool.refcount[pg] == 1
    before = pool.free_pages
    with pytest.raises(AssertionError, match="unbalanced unpin"):
        pool.unpin(pg)           # would strand the page forever
    # the failed unpin must not have freed or corrupted anything
    assert pool.free_pages == before
    pool.release(pg)             # the table's own release still works
    assert pool.free_pages == 2


def test_prefix_cache_evicts_to_pool():
    pc = PrefixCache(num_sets=1, m=1, p=4, chunk_tokens=8)  # capacity 4
    chains = [h for h in range(1, 7)]
    evicted = []
    for i, h in enumerate(chains):
        evicted += pc.insert_chain([h * 7 + 1], [i])
    assert len(evicted) == 2             # 6 inserts into capacity 4
    assert pc.stats()["evictions"] == 2


def test_batched_chain_ops_match_per_chunk_ops():
    """lookup_chains/insert_chains (one LOOKUP + one GET + one ACCESS batch)
    must produce the same pages, stats, and table as per-chunk get-until-miss
    probing — and cost a bounded number of device calls."""
    def drive(batched: bool):
        pc = PrefixCache(num_sets=8, m=2, p=4, chunk_tokens=8)
        rng = np.random.default_rng(0)
        chains = [[int(h) for h in rng.integers(1, 2**30, 3)] for _ in range(6)]
        pages, page = [], 0
        for t in range(12):
            chain = chains[t % len(chains)]
            if batched:
                got = pc.lookup_chains([chain])[0]
            else:  # per-chunk reference: probe chunk by chunk
                got = []
                for h in chain:
                    out = pc.cache.access(np.array([h], np.int32),
                                          ops=np.array([1], np.int32))  # GET
                    if not bool(out.hit[0]):
                        pc.misses += 1
                        break
                    pc.hits += 1
                    got.append(int(out.value[0, 0]))
            new = chain[len(got):]
            new_pages = list(range(page, page + len(new)))
            page += len(new)
            if batched:
                pc.insert_chains([new], [new_pages])
            else:
                for h, pg in zip(new, new_pages):
                    out = pc.cache.access(np.array([h], np.int32),
                                          np.array([[pg]], np.int32))
                    if bool(out.evicted_valid[0]):
                        pc.evictions += 1
            pages.append(got)
        return pc, pages

    a, pages_a = drive(batched=True)
    b, pages_b = drive(batched=False)
    assert pages_a == pages_b
    assert a.stats() == b.stats()
    np.testing.assert_array_equal(np.asarray(a.cache.table),
                                  np.asarray(b.cache.table))
    # 12 requests × (1 LOOKUP + ≤1 GET + ≤1 ACCESS) batches
    assert a.device_calls <= 36


@pytest.mark.slow
def test_shared_prefix_same_tick_does_not_leak_pages():
    """Two requests sharing a prefix admitted in the SAME tick both miss
    the (pre-tick) lookup and stage pages for the same chunks; the
    duplicate inserts are absorbed as hits and their pages must flow back
    to the pool instead of leaking with refcount 1."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagedKVPool(cfg, n_pages=16, page_tokens=16)
    pc = PrefixCache(num_sets=64, m=2, p=4, chunk_tokens=16)
    eng = ServeEngine(model, params, slots=2, max_len=128,
                      prefix_cache=pc, pool=pool)
    rng = np.random.default_rng(7)
    shared = rng.integers(1, cfg.vocab_size, 48 + 5).astype(np.int32)
    eng.submit(Request(rid=0, prompt=shared, max_new_tokens=2))
    eng.submit(Request(rid=1, prompt=shared.copy(), max_new_tokens=2))
    eng.run_until_done()
    # 3 chunks live in the cache; the duplicate trio was recycled
    assert pool.free_pages == 16 - 3
    assert (pool.refcount <= 1).all()


@pytest.mark.slow
def test_fully_cached_chunk_aligned_prompt_still_prefills_last_chunk():
    """A chunk-aligned prompt whose whole chain is already resident must
    not produce a zero-length continuation prefill: the engine caps reuse
    at all-but-the-last chunk."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagedKVPool(cfg, n_pages=16, page_tokens=16)
    pc = PrefixCache(num_sets=64, m=2, p=4, chunk_tokens=16)
    eng = ServeEngine(model, params, slots=1, max_len=128,
                      prefix_cache=pc, pool=pool)
    rng = np.random.default_rng(9)
    prompt = rng.integers(1, cfg.vocab_size, 48).astype(np.int32)  # 3 chunks
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=2))
    eng.run_until_done()
    eng.submit(Request(rid=1, prompt=prompt.copy(), max_new_tokens=2))
    eng.run_until_done()
    first, second = eng.finished
    assert second.prefill_skipped == 32       # 2 of 3 chunks reused
    assert second.prefill_computed == 16      # last chunk always computed
    assert second.out_tokens == first.out_tokens
    assert (pool.refcount <= 1).all()         # re-publish recycled, no leak


@pytest.mark.slow
def test_batched_admission_equals_one_at_a_time():
    """Admitting a whole tick's requests through the 3-device-call batched
    path must emit the same tokens, pin/unpin balance, and prefix-cache
    stats as admitting them one at a time — and the batched engine must
    never exceed 3 cache-engine calls per tick, at any queue depth."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(1)
    templates = [rng.integers(1, cfg.vocab_size, 32).astype(np.int32)
                 for _ in range(4)]
    # same-tick requests use distinct templates; templates recur across
    # ticks, so later admissions hit the chunks earlier ones inserted
    prompts = [np.concatenate([templates[i % 4],
                               rng.integers(1, cfg.vocab_size,
                                            5 + i).astype(np.int32)])
               for i in range(8)]

    def drive(batching: bool):
        pool = PagedKVPool(cfg, n_pages=64, page_tokens=16)
        pc = PrefixCache(num_sets=64, m=2, p=4, chunk_tokens=16)
        eng = ServeEngine(model, params, slots=2, max_len=128,
                          prefix_cache=pc, pool=pool,
                          admit_batching=batching)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=2))
        max_calls_per_tick = 0
        ticks = 0
        while (eng.queue or eng.active) and ticks < 1000:
            before = pc.device_calls
            eng.step()
            max_calls_per_tick = max(max_calls_per_tick,
                                     pc.device_calls - before)
            ticks += 1
        return eng, pool, pc, max_calls_per_tick

    eng_a, pool_a, pc_a, calls_a = drive(True)
    eng_b, pool_b, pc_b, _ = drive(False)

    assert calls_a <= 3                          # acceptance bound
    toks_a = {r.rid: r.out_tokens for r in eng_a.finished}
    toks_b = {r.rid: r.out_tokens for r in eng_b.finished}
    assert toks_a == toks_b
    skips_a = {r.rid: r.prefill_skipped for r in eng_a.finished}
    skips_b = {r.rid: r.prefill_skipped for r in eng_b.finished}
    assert skips_a == skips_b
    assert any(s > 0 for s in skips_a.values())  # reuse actually happened
    assert pc_a.stats() == pc_b.stats()
    # pin/unpin balance: nothing stays pinned once all requests retire
    np.testing.assert_array_equal(pool_a.refcount, pool_b.refcount)
    assert (pool_a.refcount <= 1).all()          # only alloc refs remain
    assert pool_a.free_pages == pool_b.free_pages


def test_fused_tick_equals_split_path_prefix_cache():
    """PrefixCache-level acceptance: ``serve_chains`` (ONE engine call per
    tick) produces bit-identical stats AND table to the split
    LOOKUP+GET+ACCESS pipeline over a multi-tick trace with cross-tick
    reuse, intra-tick shared prefixes, and evictions."""
    def drive(fused: bool):
        pc = PrefixCache(num_sets=2, m=2, p=2, chunk_tokens=8)  # capacity 8
        rng = np.random.default_rng(5)
        base = [[int(h) for h in rng.integers(1, 2**30, 3)] for _ in range(5)]
        page = 0
        ticks = []
        for t in range(16):
            chains = [base[(t + j) % len(base)] for j in range(1 + t % 2)]
            if t % 4 == 0:
                chains.append(list(chains[0]))    # intra-tick shared prefix
            if fused:
                staged = []
                for ch in chains:
                    staged.append(list(range(page, page + len(ch))))
                    page += len(ch)
                res, _ev = pc.serve_chains(chains, staged)
                ticks.append([r.hitlen for r in res])
            else:
                pages = pc.lookup_chains(chains)
                staged = []
                for ch in chains:
                    staged.append(list(range(page, page + len(ch))))
                    page += len(ch)
                pc.insert_chains(
                    [ch[len(g):] for ch, g in zip(chains, pages)],
                    [s[len(g):] for s, g in zip(staged, pages)],
                    depths=[len(g) for g in pages],
                    chain_lens=[len(ch) for ch in chains])
                ticks.append([len(g) for g in pages])
        return pc, ticks

    a, ta = drive(True)
    b, tb = drive(False)
    assert ta == tb
    assert a.stats() == b.stats()
    assert a.stats()["evictions"] > 0            # the trace really evicts
    np.testing.assert_array_equal(np.asarray(a.cache.table),
                                  np.asarray(b.cache.table))
    assert a.device_calls < b.device_calls       # 1 vs up-to-3 per tick


@pytest.mark.slow
def test_fused_admission_equals_split_batched():
    """Serving acceptance: the fused one-call tick (one ``serve_chains``
    call + one batched prefill launch per wave) emits identical tokens,
    prefix-cache stats, and pin balance to the PR-2 batched 3-call path —
    including a tick admitting two requests that share a prefix (intra-
    tick dedupe: the borrower gathers the owner's pages instead of
    recomputing, so its prefill shrinks but its tokens must not change)."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(2)
    shared = rng.integers(1, cfg.vocab_size, 40).astype(np.int32)
    other = rng.integers(1, cfg.vocab_size, 37).astype(np.int32)
    prompts = [
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, 5).astype(np.int32)]),
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, 9).astype(np.int32)]),
        other,
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, 7).astype(np.int32)]),
    ]

    def drive(mode: str):
        pool = PagedKVPool(cfg, n_pages=64, page_tokens=16)
        pc = PrefixCache(num_sets=64, m=2, p=4, chunk_tokens=16)
        eng = ServeEngine(model, params, slots=2, max_len=128,
                          prefix_cache=pc, pool=pool, admit_mode=mode)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
        max_calls = 0
        while eng.queue or eng.active:
            before = pc.device_calls
            eng.step()
            max_calls = max(max_calls, pc.device_calls - before)
        return eng, pool, pc, max_calls

    eng_a, pool_a, pc_a, calls_a = drive("fused")
    eng_b, pool_b, pc_b, calls_b = drive("split")

    assert calls_a <= 1                          # ONE engine call per tick
    assert calls_b >= 2                          # the path it replaces
    toks = lambda e: {r.rid: r.out_tokens for r in e.finished}
    assert toks(eng_a) == toks(eng_b)            # identical tokens
    assert pc_a.stats() == pc_b.stats()          # identical cache stats
    # the first tick admits rid 0+1 together: the borrower skipped the
    # shared chunks the owner prefilled (strictly more reuse than split)
    skip = lambda e, r: [x for x in e.finished if x.rid == r][0].prefill_skipped
    assert skip(eng_a, 1) > skip(eng_b, 1)
    # pin balance: everything unpinned at retirement, same pool pressure
    assert (pool_a.refcount <= 1).all() and (pool_b.refcount <= 1).all()
    assert pool_a.free_pages == pool_b.free_pages
    assert pool_a.refcount.sum() == pool_b.refcount.sum()


@pytest.mark.slow
def test_near_full_pool_reserve_commit_recycles_same_tick():
    """Reserve-then-commit under pool pressure: with a pool too small to
    stage every chunk up front, the fused tick must (a) recycle its own
    evictions for the same tick's remaining inserts via the retry pass,
    (b) keep refcounts balanced (no leaked reservations), and (c) keep
    serving correctly."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(11)
    # 6 pages; prompts of 3 chunks each -> the second tick's reservations
    # cannot all be funded until the tick's own evictions recycle
    pool = PagedKVPool(cfg, n_pages=6, page_tokens=16)
    pc = PrefixCache(num_sets=1, m=1, p=4, chunk_tokens=16)  # capacity 4
    eng = ServeEngine(model, params, slots=2, max_len=128,
                      prefix_cache=pc, pool=pool)
    for i in range(4):
        p = rng.integers(1, cfg.vocab_size, 48 + i).astype(np.int32)
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=2))
    eng.run_until_done()
    assert len(eng.finished) == 4
    assert pc.stats()["evictions"] > 0
    # no reservation leaks: free + cache-held pages account for the pool
    assert (pool.refcount >= 0).all() and (pool.refcount <= 1).all()
    assert pool.free_pages + int(pool.refcount.sum()) == pool.n_pages
    assert len(pool._reserved) == 0
    # the retry pass actually fired at least once (an extra ACCESS call
    # beyond the single fused call for some tick) — and still well under
    # the split path's 3 calls/tick
    assert pc.device_calls > 2                   # >1 call on some tick
    # the cache holds as many pages as its capacity allows (4 slots)
    held = int(pool.refcount.sum())
    assert held > 0


@pytest.mark.slow
def test_same_call_eviction_does_not_alias_pages():
    """A fused tick can insert a chunk and EVICT it again within the same
    call (set pressure).  Its page returns to the pool; the engine must
    then neither publish it to same-tick borrowers nor hand it to the
    pressure-retry pass as if it were still owned — otherwise two chunks
    alias one page and a borrower gathers the wrong KV.  Tokens must match
    the split path, which never publishes within a tick."""
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(21)
    shared = rng.integers(1, cfg.vocab_size, 48).astype(np.int32)  # 3 chunks
    prompts = [
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, 3).astype(np.int32)]),
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, 6).astype(np.int32)]),
        np.concatenate([rng.integers(1, cfg.vocab_size, 48 + 5).astype(np.int32)]),
    ]

    def drive(mode: str):
        # capacity-4 cache: 6 distinct inserts in one tick evict same-call
        # entries; 5-page pool leaves the last request partially funded so
        # the retry pass re-allocates the just-evicted page
        pool = PagedKVPool(cfg, n_pages=5, page_tokens=16)
        pc = PrefixCache(num_sets=1, m=1, p=4, chunk_tokens=16)
        eng = ServeEngine(model, params, slots=3, max_len=128,
                          prefix_cache=pc, pool=pool, admit_mode=mode)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new_tokens=2))
        eng.run_until_done()
        return eng, pool, pc

    eng_a, pool_a, pc_a = drive("fused")
    eng_b, pool_b, pc_b = drive("split")
    toks = lambda e: {r.rid: r.out_tokens for r in e.finished}
    assert toks(eng_a) == toks(eng_b)
    assert pc_a.stats()["evictions"] > 0
    assert (pool_a.refcount <= 1).all()
    assert pool_a.free_pages + int(pool_a.refcount.sum()) == pool_a.n_pages
    assert len(pool_a._reserved) == 0


def test_device_calls_counts_engine_invocations_only():
    """``device_calls`` must count ONE per engine invocation on every path
    — never per chain, per page, or per recycled duplicate-hit page."""
    pc = PrefixCache(num_sets=8, m=2, p=4, chunk_tokens=8)
    real_access = pc.cache.access
    invocations = []

    def counting_access(*a, **kw):
        invocations.append(1)
        return real_access(*a, **kw)

    pc.cache.access = counting_access
    # fused tick with duplicate staged pages absorbed as hits
    chain = [3, 5, 7]
    pc.serve_chains([chain, list(chain)], [[10, 11, 12], [20, 21, 22]])
    assert pc.device_calls == len(invocations) == 1
    # split path: lookup (1 call; nothing to promote) + insert with
    # duplicate-hit recycled pages (1 call)
    pages = pc.lookup_chains([[99, 101]])
    pc.insert_chains([[3, 99]], [[30, 31]])      # 3 is a duplicate hit
    assert pc.device_calls == len(invocations) == 3
    # promote path adds the GET batch: exactly one more call
    pc.lookup_chains([[3, 5]])
    assert pc.device_calls == len(invocations) == 5
    pc.delete(3)
    assert pc.device_calls == len(invocations) == 6


@pytest.mark.slow
def test_prefix_reuse_equals_vanilla_decode():
    cfg = get_config("phi3-mini-3.8b", smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    pool = PagedKVPool(cfg, n_pages=32, page_tokens=16)
    pc = PrefixCache(num_sets=64, m=2, p=4, chunk_tokens=16)
    eng = ServeEngine(model, params, slots=2, max_len=128,
                      prefix_cache=pc, pool=pool)
    rng = np.random.default_rng(0)
    prefix = rng.integers(1, cfg.vocab_size, 48).astype(np.int32)
    prompts = [np.concatenate([prefix,
                               rng.integers(1, cfg.vocab_size, 8 + i).astype(np.int32)])
               for i in range(3)]
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=3))
    eng.run_until_done()
    assert any(r.prefill_skipped > 0 for r in eng.finished)

    eng2 = ServeEngine(model, params, slots=1, max_len=128)
    r = Request(rid=9, prompt=prompts[2], max_new_tokens=3)
    eng2.submit(r)
    eng2.run_until_done()
    reused = [x for x in eng.finished if x.rid == 2][0]
    assert reused.out_tokens == r.out_tokens


@pytest.mark.parametrize("mode", [
    ["--kv-mode", "contiguous", "--decode-mode", "inflight"],
    ["--kv-mode", "paged", "--decode-mode", "megastep"],
])
def test_serve_entry_point(mode):
    """``launch.serve.serve`` — the path ``chip_smoke.py`` drives in-process
    — serves every request, with prefill skipped by the prefix cache."""
    from repro.launch.serve import build_parser, serve
    args = build_parser().parse_args(
        ["--requests", "4", "--templates", "1", "--max-new", "3",
         "--layers", "1", "--slots", "2", "--max-len", "128",
         "--pool-pages", "32"] + mode)
    eng = serve(args)
    assert eng.model.cfg.n_layers == 1
    assert sorted(r.rid for r in eng.finished) == [0, 1, 2, 3]
    assert all(len(r.out_tokens) == 3 for r in eng.finished)
    assert sum(r.prefill_skipped for r in eng.finished) > 0


def test_serve_smoke_flag():
    from repro.launch.serve import build_parser
    ap = build_parser()
    assert ap.parse_args([]).smoke
    assert not ap.parse_args(["--no-smoke"]).smoke
