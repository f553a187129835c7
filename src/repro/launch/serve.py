"""Serving entry point: ``python -m repro.launch.serve --arch <id> [--no-smoke]``.

Brings up the continuous-batching engine with the multi-step-LRU prefix
cache and runs a synthetic request workload (shared-prefix templates with
zipfian popularity — the cache's favourable regime, and exactly the shape
of production prompt traffic).  ``serve(args)`` is the same run in-process
(``chip_smoke.py`` drives it that way).
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np
import jax

from repro.configs import get_config
from repro.models.model import make_model
from repro.serving.engine import Request, ServeEngine
from repro.serving.kv_cache import PagedKVPool
from repro.serving.prefix_cache import PrefixCache
from repro.data.ycsb import zipfian
from repro.launch.compile_cache import use_compile_cache


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="phi3-mini-3.8b")
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the arch's toy SMOKE config (default); "
                         "--no-smoke runs its published widths")
    ap.add_argument("--layers", type=int, default=0,
                    help="cut the depth to this many layers (0 = the "
                         "config's own depth)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--pool-pages", type=int, default=256,
                    help="pages of --chunk-tokens tokens in the KV pool")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--templates", type=int, default=8)
    ap.add_argument("--prefix-tokens", type=int, default=64)
    ap.add_argument("--chunk-tokens", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--no-prefix-cache", action="store_true")
    ap.add_argument("--decode-mode",
                    choices=["inflight", "roundrobin", "megastep"],
                    default="inflight",
                    help="inflight: one decode launch/tick advances every "
                         "slot at its own length; roundrobin: legacy "
                         "min-length schedule (equivalence oracle); "
                         "megastep: fuse K pure-decode ticks into one "
                         "device-side scan with on-chip EOS masking and "
                         "one host sync per window (token-identical to "
                         "inflight)")
    ap.add_argument("--max-window", type=int, default=16, metavar="K",
                    help="megastep window cap (compile-size bound; scan "
                         "lengths pad to pow2 buckets)")
    ap.add_argument("--kv-mode", choices=["contiguous", "paged"],
                    default="contiguous",
                    help="contiguous: gather cached prefix pages into each "
                         "slot's private KV (a device copy per borrower; "
                         "the bit-exactness oracle); paged: decode walks a "
                         "per-slot block table straight over the shared "
                         "pool — zero gather copies, one resident copy of "
                         "a hot prefix however many slots borrow it "
                         "(requires the prefix cache)")
    ap.add_argument("--paged-kernel", action="store_true",
                    help="paged decode attention through the Pallas kernel "
                         "instead of its jnp mirror (needs --kv-mode paged)")
    ap.add_argument("--sharded", type=int, default=0, metavar="D",
                    help="back the prefix cache with a ShardedCacheClient "
                         "over D devices: on a TPU host, D real chips; on "
                         "the CPU, forced host devices (XLA_FLAGS="
                         "--xla_force_host_platform_device_count=D)")
    ap.add_argument("--cap", type=float, default=0.0,
                    help="per-peer cap multiplier for --sharded "
                         "(0 = 'full', no shedding)")
    ap.add_argument("--placement", choices=["load", "roundrobin", "split"],
                    default=None,
                    help="sharded chain placement (default: split under a "
                         "bounded --cap, load otherwise); split packs "
                         "chunk fragments across slabs and sheds only the "
                         "un-placeable suffix")
    ap.add_argument("--throttle-threshold", type=float, default=0.0,
                    help="owner-aware admission throttling: defer NEW "
                         "admissions whose home slabs report pressure >= "
                         "this EWMA level (0 = off; needs --sharded)")
    ap.add_argument("--chaos-seed", type=int, default=-1,
                    help="run under a seeded FaultPlan (requires --sharded); "
                         "faults apply at tick boundaries")
    ap.add_argument("--chaos-events", type=int, default=3,
                    help="events in the seeded FaultPlan")
    return ap


def check_args(ap: argparse.ArgumentParser, args) -> None:
    if args.kv_mode == "paged" and args.no_prefix_cache:
        ap.error("--kv-mode paged requires the prefix cache (the pool is "
                 "the resident prefix store)")
    if args.paged_kernel and args.kv_mode != "paged":
        ap.error("--paged-kernel needs --kv-mode paged")
    if args.throttle_threshold > 0 and not args.sharded:
        ap.error("--throttle-threshold needs --sharded (pressure comes "
                 "from the sharded backend's load mirror)")
    if args.chaos_seed >= 0 and not args.sharded:
        ap.error("--chaos-seed needs --sharded (fault targets)")


def build_model(args):
    """(model, params) for ``args``: the arch config (SMOKE or published
    widths, depth cut by ``--layers``) with random weights from seed 0,
    drawn inside one jitted init so the f32 draws never sit on the device
    all at once."""
    cfg = get_config(args.arch, smoke=args.smoke)
    if args.layers:
        cfg = dataclasses.replace(cfg, n_layers=args.layers)
    model = make_model(cfg)
    params = jax.jit(model.init)(jax.random.PRNGKey(0))
    return model, params


def serve(args, model=None, params=None) -> ServeEngine:
    """Serve the synthetic workload of ``args`` to completion; returns the
    drained engine (``engine.finished`` holds the requests)."""
    if model is None:
        model, params = build_model(args)
    cfg = model.cfg
    # the cache kernel runs where it compiles; on the CPU its jnp mirror
    # (bit-identical) stands in for the Pallas interpreter
    cache_kernel = jax.default_backend() == "tpu"

    pool = pc = None
    if not args.no_prefix_cache:
        pool = PagedKVPool(cfg, n_pages=args.pool_pages,
                           page_tokens=args.chunk_tokens)
        backend = None
        if args.sharded:
            from repro.core.multistep import MSLRUConfig
            from repro.core.sharded import ShardedCacheClient
            from repro.launch.mesh import make_cache_mesh
            backend = ShardedCacheClient(
                MSLRUConfig(num_sets=256, m=2, p=4, value_planes=1),
                make_cache_mesh(args.sharded), use_kernel=cache_kernel,
                cap=(args.cap if args.cap > 0 else "full"),
                placement=args.placement)
        pc = PrefixCache(num_sets=256, m=2, p=4,
                         chunk_tokens=args.chunk_tokens, backend=backend,
                         use_kernel=cache_kernel)
    eng = ServeEngine(model, params, slots=args.slots, max_len=args.max_len,
                      prefix_cache=pc, pool=pool,
                      decode_mode=args.decode_mode, kv_mode=args.kv_mode,
                      paged_kernel=args.paged_kernel,
                      max_window=args.max_window,
                      throttle_threshold=(args.throttle_threshold
                                          if args.throttle_threshold > 0
                                          else None))

    plan = None
    if args.chaos_seed >= 0:
        from repro.launch.elastic import FaultPlan
        plan = FaultPlan.seeded(args.chaos_seed, ticks=args.requests,
                                ndev=args.sharded,
                                n_events=args.chaos_events)
        print(f"[serve] fault plan: {plan.events}")

    rng = np.random.default_rng(0)
    templates = [rng.integers(1, cfg.vocab_size, args.prefix_tokens).astype(np.int32)
                 for _ in range(args.templates)]
    picks = zipfian(args.templates, args.requests, alpha=1.0, seed=1) - 1

    t0 = time.time()
    for i in range(args.requests):
        suffix = rng.integers(1, cfg.vocab_size, 4 + i % 13).astype(np.int32)
        prompt = np.concatenate([templates[int(picks[i]) % args.templates], suffix])
        eng.submit(Request(rid=i, prompt=prompt, max_new_tokens=args.max_new))
    ticks = eng.run_until_done(fault_plan=plan)
    dt = time.time() - t0
    if plan is not None:
        print(f"[serve] faults applied: {eng.fault_log}, "
              f"fallbacks={eng.fallbacks}")

    skipped = sum(r.prefill_skipped for r in eng.finished)
    computed = sum(r.prefill_computed for r in eng.finished)
    print(f"[serve] {len(eng.finished)} requests in {ticks} ticks, {dt:.1f}s")
    print(f"[serve] prefill tokens: computed={computed} skipped={skipped} "
          f"({skipped/(skipped+computed):.1%} saved)")
    st = eng.stats()
    print(f"[serve] decode: {st['decode_launches']} launches, "
          f"{st['decode_tokens']} tokens, "
          f"{st['launches_per_token']:.3f} rows/token, admit wait "
          f"p50/p99 {st['service_ticks_p50']:.0f}/"
          f"{st['service_ticks_p99']:.0f} ticks")
    if args.decode_mode == "megastep":
        print(f"[serve] megastep: {st['megastep_windows']} windows "
              f"(mean {st['mean_window']:.1f} ticks, cap "
              f"{st['max_window']}), host_syncs={st['host_syncs']} "
              f"({st['host_syncs_per_token']:.3f}/token), drain "
              f"rows/token={st['drain_launches_per_token']:.3f}")
    print(f"[serve] kv: mode={st['kv_mode']} "
          f"gather_calls={st['gather_calls']} "
          f"resident_kv_peak={st['resident_kv_tokens_peak']} tok "
          f"({st['resident_kv_bytes_peak'] / 2**20:.1f} MiB)")
    if args.sharded:
        print(f"[serve] sharded: placement="
              f"{pc.cache.placement} "
              f"split_chains={st['split_chains']} "
              f"partial_sheds={st['partial_sheds']} "
              f"partial_served={st['partial_served']} "
              f"slab_occupancy_peak={st['slab_occupancy_peak']:.2f} "
              f"throttled={st['throttled_admissions']} "
              f"fallback_rate={st['fallback_rate']:.3f}")
    if pc:
        print(f"[serve] prefix cache: {pc.stats()}")
    return eng


def main(argv=None):
    ap = build_parser()
    args = ap.parse_args(argv)
    check_args(ap, args)
    use_compile_cache()
    serve(args)


if __name__ == "__main__":
    main()
