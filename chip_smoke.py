#!/usr/bin/env python3
"""Smoke run of the main path on one TPU chip (``--chips 4``: the sharded path).

    python3 chip_smoke.py              # phases a-c on one chip
    python3 chip_smoke.py --chips 4    # the sharded cache and serving path

One process drives the chip; there is no CPU fallback.  Phases, in order,
each failing loudly at its first fault:

  a. device  — the first JAX device is a TPU, else exit 2 before any phase.
  b. cache   — ``MultiStepLRUCache`` (one-pass engine, compiled Pallas
               kernel) over 2**22 sets x 8 lanes (33.5M items) fed Zipf-0.99
               keys over twice the item capacity; hits, values and the final
               table must be bit-equal to the sequential engine fed the same
               stream on the chip.
  c. serving — phi3-mini-3.8b at its published widths through
               ``repro.launch.serve.serve`` (prefix cache on the cache
               kernel, paged KV pool, ``ServeEngine``): contiguous KV with
               in-flight decode at full depth, then paged KV with megastep
               decode, through the paged-attention kernel and through its
               jnp mirror; plus one paged decode call, kernel against mirror.

With ``--chips 4`` only the sharded path runs: the phase b stream through
the sharded one-pass engine over a 4-chip mesh, bit-equal to the
single-device engine, and a few serving requests with a 4-chip
``ShardedCacheClient`` behind the prefix cache, token-identical to one chip.

The last line of stdout is ``{"ok": true, "device": {...}}``; everything
measured (seconds, compile seconds, peak device memory, hit ratio, tokens)
is printed on the lines before it.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import numpy as np  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

# phase b: the cache core
NUM_SETS = 2**22
BATCH = 8192
N_BATCHES = 4
SEED = 0
# phase c: phi3-mini serving
SLOTS, MAX_LEN, POOL_PAGES, PAGE_TOKENS = 4, 1024, 256, 16
# megastep decode at 32 layers needs 17.9 GB of HBM (compiled for a
# described v5e, whose HBM is 16 GB): the scan holds three copies of the
# undonated slot KV.  The paged megastep runs cut depth to fit.
MEGASTEP_LAYERS = 20
# paged kernel vs its jnp mirror, one decode call: the mirror rounds scores
# and probabilities to bf16 where the kernel keeps f32
PAGED_RTOL = 0.02

COUNTERS = {"compile_s": 0.0, "cache_hits": 0}


def _watch_compiles():
    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            COUNTERS["compile_s"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            COUNTERS["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


class Phase:
    """Times a phase and reports its compile seconds and device memory."""

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = COUNTERS["compile_s"]
        print(f"[{self.name}] start", flush=True)
        return self

    def __exit__(self, *exc):
        if exc[0] is not None:
            return False
        stats = jax.devices()[0].memory_stats() or {}
        peak = stats.get("peak_bytes_in_use")
        print(f"[{self.name}] done in {time.perf_counter() - self.t0:.2f} s, "
              f"compile {COUNTERS['compile_s'] - self.c0:.2f} s, "
              f"device 0 peak_bytes_in_use "
              f"{'n/a' if peak is None else f'{peak / 2**30:.2f} GiB'}",
              flush=True)
        return False


def _stream(cfg, n_queries, seed):
    """Zipf-0.99 keys over twice the item capacity, and two value planes
    derived from each key."""
    from repro.data.ycsb import zipfian
    keys = zipfian(2 * cfg.capacity, n_queries, alpha=0.99, seed=seed)
    vals = np.stack([keys, keys ^ 0x5BD1E995], axis=1).astype(np.int32)
    return keys[:, None], vals


def _assert_equal(name, want, got):
    want, got = np.asarray(want), np.asarray(got)
    bad = int((want != got).sum())
    assert bad == 0, f"{name}: {bad} of {want.size} entries differ"


def phase_cache(num_sets=NUM_SETS, batch=BATCH, n_batches=N_BATCHES,
                seed=SEED, expect_kernel=True):
    from repro.core import MultiStepLRUCache
    from repro.core.engine import make_sequential_engine
    from repro.core.multistep import MSLRUConfig, init_table

    cfg = MSLRUConfig(num_sets=num_sets, m=2, p=4, value_planes=2)
    keys, vals = _stream(cfg, batch * n_batches, seed)
    print(f"[cache] {cfg.num_sets} sets x {cfg.assoc} lanes = "
          f"{cfg.capacity} items, table "
          f"{cfg.num_sets * cfg.assoc * cfg.planes * 4 / 1e6:.1f} MB; "
          f"{n_batches} batches of {batch} Zipf-0.99 keys over "
          f"{2 * cfg.capacity} distinct", flush=True)
    cache = MultiStepLRUCache(cfg, engine="onepass", use_kernel=True)
    if expect_kernel:
        hlo = jax.jit(cache._batched).lower(
            cache.table, jnp.asarray(keys[:batch]),
            jnp.asarray(vals[:batch])).compile().as_text()
        assert "tpu_custom_call" in hlo, "the cache kernel was not compiled"
    hits, values, pos = [], [], []
    t_batches = []
    for i in range(n_batches):
        sl = slice(i * batch, (i + 1) * batch)
        t = time.perf_counter()
        res = cache.access(keys[sl], vals[sl])
        # the call's table is ready with res; reading cache.table here
        # would lend it out, and the next call would copy it
        jax.block_until_ready(res)
        t_batches.append(time.perf_counter() - t)
        hits.append(np.asarray(res.hit))
        values.append(np.asarray(res.value))
        pos.append(np.asarray(res.pos))
    hits = np.concatenate(hits)
    print(f"[cache] batch seconds (first includes compile): "
          f"{[round(x, 4) for x in t_batches]}", flush=True)
    print(f"[cache] hit ratio {hits.mean():.4f} over {hits.size} queries, "
          f"occupancy {cache.occupancy:.4f}", flush=True)

    seq = make_sequential_engine(cfg)
    t = time.perf_counter()
    table_seq, out = seq(init_table(cfg), jnp.asarray(keys),
                         jnp.asarray(vals))
    jax.block_until_ready(table_seq)
    print(f"[cache] sequential oracle {time.perf_counter() - t:.2f} s "
          f"(with compile)", flush=True)
    _assert_equal("hits", out.hit, hits)
    _assert_equal("pos", out.pos, np.concatenate(pos))
    _assert_equal("values", out.value, np.concatenate(values))
    _assert_equal("table", table_seq, cache.table)
    print("[cache] hits, positions, values and table bit-equal to the "
          "sequential engine", flush=True)


def _serve_args(**over):
    from repro.launch.serve import build_parser
    argv = ["--no-smoke", "--slots", str(SLOTS), "--max-len", str(MAX_LEN),
            "--pool-pages", str(POOL_PAGES), "--chunk-tokens",
            str(PAGE_TOKENS), "--requests", "8", "--templates", "2",
            "--prefix-tokens", "256", "--max-new", "16"]
    args = build_parser().parse_args(argv)
    for k, v in over.items():
        setattr(args, k, v)
    return args


def _serve_run(label, args, model, params):
    from repro.launch.serve import serve
    t = time.perf_counter()
    eng = serve(args, model, params)
    secs = time.perf_counter() - t
    reqs = {r.rid: r for r in eng.finished}
    assert len(reqs) == args.requests, (
        f"{label}: {len(reqs)} of {args.requests} requests finished")
    skipped = sum(r.prefill_skipped for r in reqs.values())
    assert skipped > 0, f"{label}: the prefix cache skipped no prefill"
    tokens = {rid: list(r.out_tokens) for rid, r in reqs.items()}
    n_tok = sum(len(t) for t in tokens.values())
    print(f"[serve:{label}] {len(reqs)} requests, {n_tok} tokens in "
          f"{secs:.2f} s (with compile), prefill skipped {skipped}",
          flush=True)
    del eng
    gc.collect()
    return tokens


def _token_diff(a, b):
    return sum(x != y for rid in a for x, y in zip(a[rid], b[rid])) + sum(
        abs(len(a[rid]) - len(b[rid])) for rid in a)


def check_paged_kernel(cfg, params, slots=SLOTS, max_len=MAX_LEN,
                       pages=POOL_PAGES, page_tokens=PAGE_TOKENS):
    """One paged decode call at these widths: kernel against jnp mirror."""
    from repro.models.attention import paged_attn_decode
    kvh, dh = cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(jax.random.PRNGKey(SEED + 7), 5)
    bf16 = jnp.bfloat16
    x = jax.random.normal(ks[0], (slots, 1, cfg.d_model), bf16)
    pool_k = jax.random.normal(ks[1], (pages, page_tokens, kvh, dh), bf16)
    pool_v = jax.random.normal(ks[2], (pages, page_tokens, kvh, dh), bf16)
    tail_k = jax.random.normal(ks[3], (slots, max_len, kvh, dh), bf16)
    tail_v = jax.random.normal(ks[4], (slots, max_len, kvh, dh), bf16)
    n_bt = max_len // page_tokens
    bt = jnp.asarray(np.random.default_rng(SEED).integers(
        0, pages, (slots, n_bt)), jnp.int32)
    # prefixes of 0..n_bt/2 pages; each row then attends to most of smax
    plen = jnp.asarray([(i * n_bt // 2 // max(slots - 1, 1)) * page_tokens
                        for i in range(slots)], jnp.int32)
    cur = jnp.full((slots,), max_len - 1 - page_tokens, jnp.int32)
    attn_p = jax.tree.map(lambda w: w[0], params["blocks"]["attn"])

    @jax.jit
    def both(p, *xs):
        kw = dict(smax=max_len, n_heads=cfg.n_heads, n_kv_heads=kvh,
                  d_head=dh, rope_kind=cfg.rope_kind, theta=cfg.rope_theta)
        mirror = paged_attn_decode(p, *xs, use_kernel=False, **kw)[0]
        kernel = paged_attn_decode(p, *xs, use_kernel=True, **kw)[0]
        return mirror, kernel

    mirror, kernel = both(attn_p, x, pool_k, pool_v, bt, tail_k, tail_v,
                          plen, cur)
    mirror = np.asarray(mirror, np.float32)
    kernel = np.asarray(kernel, np.float32)
    err = float(np.abs(kernel - mirror).max())
    scale = float(np.abs(mirror).max())
    print(f"[serve] paged kernel vs jnp mirror, one decode call: max |diff| "
          f"{err:.4g} against max |mirror| {scale:.4g} (tolerance "
          f"{PAGED_RTOL} x max |mirror|)", flush=True)
    assert np.isfinite(kernel).all(), "paged kernel output not finite"
    assert err <= PAGED_RTOL * scale, "paged kernel disagrees with mirror"


def phase_serving(megastep_layers=MEGASTEP_LAYERS, **over):
    """Serve the workload three times: contiguous in-flight at full depth,
    then paged megastep (mirror, kernel) at ``megastep_layers``."""
    from repro.launch.serve import build_model

    args = _serve_args(decode_mode="inflight", kv_mode="contiguous", **over)
    model, params = build_model(args)
    cfg = model.cfg
    print(f"[serve] {cfg.name}: d_model {cfg.d_model}, {cfg.n_heads} heads "
          f"x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, "
          f"{cfg.n_layers} layers", flush=True)
    _serve_run("contiguous inflight", args, model, params)
    check_paged_kernel(cfg, params, slots=args.slots, max_len=args.max_len,
                       pages=args.pool_pages, page_tokens=args.chunk_tokens)
    del model, params
    gc.collect()

    if megastep_layers < cfg.n_layers:
        print(f"[serve] depth cut {cfg.n_layers} -> {megastep_layers} "
              f"layers for the paged megastep runs (HBM)", flush=True)
    args = _serve_args(layers=min(megastep_layers, cfg.n_layers),
                       decode_mode="megastep", kv_mode="paged", **over)
    model, params = build_model(args)
    mirror = _serve_run("paged megastep mirror", args, model, params)
    args.paged_kernel = True
    kernel = _serve_run("paged megastep kernel", args, model, params)
    print(f"[serve] paged kernel run: {_token_diff(mirror, kernel)} of "
          f"{sum(len(t) for t in mirror.values())} tokens differ from the "
          f"paged mirror run", flush=True)


def phase_sharded(ndev, num_sets=NUM_SETS, batch=BATCH, n_batches=N_BATCHES,
                  serve_layers=4, **serve_over):
    """The phase b stream through the sharded one-pass engine over ``ndev``
    chips against the single-device engine, then serving with a sharded
    prefix-cache backend against one chip."""
    from repro.core.engine import make_batched_engine
    from repro.core.multistep import MSLRUConfig, init_table
    from repro.core.sharded import make_sharded_engine, shard_table
    from repro.launch.mesh import make_cache_mesh
    from repro.launch.serve import build_model

    with Phase("sharded cache"):
        cfg = MSLRUConfig(num_sets=num_sets, m=2, p=4, value_planes=2)
        keys, vals = _stream(cfg, batch * n_batches, SEED)
        mesh = make_cache_mesh(ndev)
        sharded = make_sharded_engine(cfg, mesh, cap="full",
                                      engine="onepass", use_kernel=True)
        single = make_batched_engine(cfg, engine="onepass", use_kernel=True)
        t_sh = shard_table(init_table(cfg), mesh)
        devs = {s.device for s in t_sh.addressable_shards}
        shapes = {s.data.shape for s in t_sh.addressable_shards}
        print(f"[sharded cache] table {t_sh.shape} over {len(devs)} devices "
              f"({t_sh.sharding.spec}), shard shapes {shapes}", flush=True)
        assert len(devs) == ndev, f"table spans {len(devs)} devices"
        t_one = init_table(cfg)
        hit_sh, hit_one = [], []
        for i in range(n_batches):
            sl = slice(i * batch, (i + 1) * batch)
            qk, qv = jnp.asarray(keys[sl]), jnp.asarray(vals[sl])
            t = time.perf_counter()
            t_sh, h, _, served = sharded(t_sh, qk, qv)
            jax.block_until_ready(t_sh)
            dt = time.perf_counter() - t
            assert bool(np.asarray(served).all()), "cap='full' shed a query"
            t_one, res = single(t_one, qk, qv)
            hit_sh.append(np.asarray(h))
            hit_one.append(np.asarray(res.hit))
            print(f"[sharded cache] batch {i}: {dt:.4f} s", flush=True)
        assert len({s.device for s in t_sh.addressable_shards}) == ndev
        _assert_equal("sharded hits", np.concatenate(hit_one),
                      np.concatenate(hit_sh))
        _assert_equal("sharded table", t_one, t_sh)
        print(f"[sharded cache] table and hits over {ndev} devices bit-equal "
              f"to the single-device engine; hit ratio "
              f"{np.concatenate(hit_sh).mean():.4f}", flush=True)
        del t_sh, t_one
        gc.collect()

    with Phase("sharded serving"):
        args = _serve_args(layers=serve_layers, decode_mode="inflight",
                           kv_mode="contiguous", **serve_over)
        model, params = build_model(args)
        print(f"[sharded serving] {model.cfg.name} at its widths, depth cut "
              f"to {model.cfg.n_layers} layers", flush=True)
        one = _serve_run("prefix cache on one device", args, model, params)
        args.sharded = ndev
        many = _serve_run(f"prefix cache over {ndev} devices", args, model,
                          params)
        diff = _token_diff(one, many)
        assert diff == 0, f"{diff} tokens differ between 1 and {ndev} devices"
        print(f"[sharded serving] tokens identical with the cache over "
              f"{ndev} devices and on one", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=[1, 4], default=1)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    count = len(jax.devices())
    print(f"[device] {dev.platform} {dev.device_kind} x {count}", flush=True)
    if count < args.chips:
        print(f"chip_smoke: --chips {args.chips} but {count} devices",
              file=sys.stderr)
        return 2

    from repro.launch.compile_cache import use_compile_cache
    cache_dir = Path(use_compile_cache())
    print(f"[device] compile cache: {cache_dir}", flush=True)
    _watch_compiles()
    t0 = time.perf_counter()
    if args.chips == 1:
        with Phase("cache"):
            phase_cache()
        gc.collect()
        with Phase("serving"):
            phase_serving()
    else:
        phase_sharded(args.chips)
    entries = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    print(f"[done] {time.perf_counter() - t0:.2f} s, compile "
          f"{COUNTERS['compile_s']:.2f} s, persistent cache hits "
          f"{COUNTERS['cache_hits']}, {entries} entries in {cache_dir}",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
