"""Capacity-bounded sharded serving: the shed-rate / buffer-memory /
hit-ratio trade-off at D=8.

The sharded cache engine bounds per-shard work with fixed-capacity
all_to_all slabs (the set-associative independence argument, lifted to
chips).  ``cap="full"`` never sheds but sizes every per-peer buffer to the
whole slab — O(ndev × slab) memory per device.  A bounded cap shrinks the
buffers to ``cap × ndev`` rows but sheds chains when a tick's routing
overflows a shard (Zipfian traffic concentrates same-template chains onto
one home shard); the serving tier retries sheds next tick, so the question
is how much hit ratio survives and how often chains wait.

This bench sweeps cap ∈ {full, 4×, 2×, 1×, 0.5×} of the expected per-peer
load on a Zipfian template trace served through ``PrefixCache`` on a
``ShardedCacheClient`` over 8 forced host devices (subprocess, like
fig14), with a next-tick retry queue (max 3 retries, then the chain is
served PLAIN — counted as a ``fallback``, never dropped: the elastic
serving contract is that faults and caps cost goodput, not answers).
Output per cap: shed rate (shed chain-events / chain submissions),
retried/fallback counts, goodput (completed chains per tick), chunk hit
ratio, and the per-device all_to_all send-buffer bytes.

Elastic entries ride the same trace: ``2x-deg`` / ``full-deg`` lose
shard 0 a quarter of the way in (``mark_degraded`` — orphaned chains
re-prefill or fall back; placement stops targeting the dead slab) and
``2x-resize`` live-reshards the mesh 8→4 halfway through (drain +
canonical re-insert, serving resumes on the rebuilt table).  These are
the robustness curve: how much goodput survives a lost shard or a live
resize, with ZERO dropped requests by construction.

Placement: ``placement="load"`` packs each chain whole onto the slab
whose home shards it stresses least (judged on the same per-(slab,
owner) counts the shed pre-check mirrors); the ``2x-rr`` / ``1x-rr``
entries re-run those caps with the legacy round-robin deal, so the
committed curve shows the shed-rate drop load-aware packing buys at
bounded caps.  The ``1x-split`` / ``2x-deg-split`` entries run
``placement="split"``: chains that fit no single slab split into chunk
fragments across slabs, shedding only the un-placeable SUFFIX — the
serve completes at the fragment boundary and only the tail inserts
re-run next tick, so the permanent plain-prefill fallbacks of the 1×
cliff (and of a lost shard's survivors) mostly disappear.  ``throttle``
adds owner-aware admission deferral on top (fresh chains homing on a
slab whose pressure EWMA exceeds ``THROTTLE_THRESH`` wait up to
``DEFER_MAX`` ticks).  Tokens/tables are placement-independent
(canonical ``order`` ranks) — only shed luck changes.

``run()`` merges the curve into BENCH_sharded.json at the repo root;
``--smoke`` uses a tiny trace (entry block ``smoke``, the CI gate trace);
``--check`` recomputes the smoke curve and fails (exit 1) if the shed rate
at cap=2×expected exceeds the committed entry by >20%, any hit ratio
drifts from the committed value, any fault entry drops a request, or a
fault entry's goodput falls below 1/1.2× of the committed number.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

from benchmarks.common import cached

NDEV = 8
# (name, cap, placement, fault, throttle): fault None = steady-state;
# "degrade" = mark_degraded(0) at TICKS//4; "resize" = live reshard
# 8 -> 4 at TICKS//2.  throttle=1 defers fresh chains whose home shards
# report chain_pressure >= THROTTLE_THRESH (owner-aware admission).
CAPS = [("full", "full", "load", None, 0), ("4x", 4.0, "load", None, 0),
        ("2x", 2.0, "load", None, 0), ("1x", 1.0, "load", None, 0),
        ("0.5x", 0.5, "load", None, 0),
        ("2x-rr", 2.0, "roundrobin", None, 0),
        ("1x-rr", 1.0, "roundrobin", None, 0),
        ("1x-split", 1.0, "split", None, 0),
        ("full-deg", "full", "load", "degrade", 0),
        ("2x-deg", 2.0, "load", "degrade", 0),
        ("2x-deg-split", 2.0, "split", "degrade", 0),
        ("2x-resize", 2.0, "load", "resize", 0),
        ("throttle", 1.0, "split", None, 1)]
N_TEMPLATES = 96
PREFIX_CHUNKS = 4
CHAINS_PER_TICK = 32
TICKS = 200
SMOKE_TICKS = 30
CACHE_SETS = 32          # 32 sets * 8 lanes = 256 slots vs 384 hot chunks
MAX_RETRIES = 3
THROTTLE_THRESH = 0.75   # defer fresh chains above this home-slab pressure
DEFER_MAX = 5            # ... for at most this many ticks (starvation cap)

BENCH_JSON = Path(__file__).resolve().parent.parent / "BENCH_sharded.json"

_CHILD = r"""
import os, sys, json
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(ndev)d"
os.environ["JAX_PLATFORMS"] = "cpu"  # forced host devices exist only on the CPU
sys.path.insert(0, "src")
import numpy as np
from repro.core import MSLRUConfig
from repro.core.sharded import ShardedCacheClient
from repro.data.ycsb import zipfian
from repro.launch.mesh import make_cache_mesh
from repro.serving.prefix_cache import PrefixCache

NDEV = %(ndev)d
TICKS = %(ticks)d
B = %(chains_per_tick)d
PC = %(prefix_chunks)d
MAX_RETRIES = %(max_retries)d
THROTTLE_THRESH = %(throttle_thresh)f
DEFER_MAX = %(defer_max)d

mesh = make_cache_mesh(NDEV)
rng = np.random.default_rng(17)
templates = [[(int(h) & 0x7FFFFFFF) | 1
              for h in rng.integers(1, 2**30, PC)]
             for _ in range(%(n_templates)d)]
picks = zipfian(%(n_templates)d, TICKS * B, alpha=1.0, seed=18) - 1

out = {}
for name, cap, placement, fault, throttle in %(caps)r:
    cap = float(cap) if isinstance(cap, (int, float)) else cap
    mcfg = MSLRUConfig(num_sets=%(cache_sets)d, m=2, p=4, value_planes=1)
    client = ShardedCacheClient(mcfg, mesh, cap=cap, placement=placement)
    pc = PrefixCache(chunk_tokens=16, backend=client)
    page = 0
    retry = []            # (chain, tries)
    pending = []          # split tails: (hashes, pages, depth, chain_len)
    deferred = []         # throttle: (chain, ticks_deferred)
    submissions = completed = fallbacks = fresh = throttled = 0
    orphans = 0
    max_buf = (0, 0)
    i = 0
    t = 0
    while True:
        # retries go first (next-tick priority), deferred chains whose
        # home shards cooled off (or waited DEFER_MAX ticks) come back,
        # fresh requests fill to B; the loop runs past TICKS until every
        # queue drains, so every submitted chain finishes — zero drops
        if fault == "degrade" and t == TICKS // 4:
            orphans = len(client.mark_degraded(0))
        if fault == "resize" and t == TICKS // 2:
            client.reshard(NDEV // 2)
        if pending:
            # the ServeEngine analogue: a split-placed chain's shed tail
            # inserts re-run at the next tick boundary, one batched call
            pc.insert_chains([p[0] for p in pending],
                             [p[1] for p in pending],
                             depths=[p[2] for p in pending],
                             chain_lens=[p[3] for p in pending])
            pending = []
        todo = retry
        retry = []
        if deferred:
            still = []
            for ch, dt in deferred:
                if (len(todo) < B
                        and (dt >= DEFER_MAX
                             or client.chain_pressure(ch) < THROTTLE_THRESH)):
                    todo.append((ch, 0))
                else:
                    still.append((ch, dt + 1))
            deferred = still
        draining = i >= TICKS * B
        while len(todo) < B and i < TICKS * B:
            ch = templates[int(picks[i]) %% len(templates)]
            i += 1
            fresh += 1
            if (throttle
                    and client.chain_pressure(ch) >= THROTTLE_THRESH):
                deferred.append((ch, 0))
                throttled += 1
                continue
            todo.append((ch, 0))
        if not todo and not deferred and not pending:
            break
        if not todo:
            t += 1
            continue
        chains = [list(c) for c, _ in todo]
        staged = []
        for ch in chains:
            staged.append(list(range(page, page + len(ch))))
            page += len(ch)
        res, _ev = pc.serve_chains(chains, staged,
                                   retries=[n > 0 for _, n in todo])
        submissions += len(chains)
        q, k, planes = client.route_shape
        max_buf = max(max_buf, (NDEV * k * planes * 4, k))
        for (ch, n), sg, r in zip(todo, staged, res):
            if r.shed:
                # n+1 sheds so far; allow MAX_RETRIES retries (mirroring
                # ServeEngine.max_shed_retries), then serve PLAIN — the
                # chain completes cache-less, it is never dropped
                if n + 1 > MAX_RETRIES:
                    fallbacks += 1
                    pc.note_fallback()
                    completed += 1
                else:
                    retry.append((ch, n + 1))
            else:
                # split placement: a fragment-boundary serve completes the
                # request THIS tick (the engine prefills the tail); only
                # the tail chunk inserts re-run next tick
                sl = r.served_len
                if sl is not None and sl < len(ch):
                    pending.append((list(ch)[sl:], sg[sl:], sl, len(ch)))
                completed += 1
        t += 1
    # distinct chains in minus chains out: the drain loop makes this 0
    # (submissions counts ATTEMPTS — the shed_rate denominator)
    dropped = fresh - completed
    st = pc.stats()
    out[name] = {
        "cap": cap if cap == "full" else float(cap),
        "placement": placement,
        "fault": fault,
        "throttle": throttle,
        "shed_rate": st["shed"] / submissions if submissions else 0.0,
        "shed": st["shed"],
        "retried": st["retried"],
        "dropped": dropped,
        "fallbacks": fallbacks,
        "fallback_rate": fallbacks / completed if completed else 0.0,
        "completed": completed,
        "goodput": completed / t if t else 0.0,
        "ticks_run": t,
        "orphans": orphans,
        "submissions": submissions,
        "hit_ratio": st["hit_ratio"],
        "hits": st["hits"],
        "misses": st["misses"],
        "evictions": st["evictions"],
        "partial_served": st["partial_served"],
        "split_chains": client.split_chains,
        "partial_sheds": client.partial_sheds,
        "throttled": throttled,
        "slab_occupancy_peak": client.slab_occupancy_peak,
        "send_buffer_bytes": max_buf[0],
        "k_depth": max_buf[1],
        "client_shed_rows": client.sheds,
        "degraded_sheds": client.degraded_sheds,
    }
print(json.dumps(out))
"""


def _sweep(ticks: int) -> dict:
    src = _CHILD % {
        "ndev": NDEV, "ticks": ticks, "chains_per_tick": CHAINS_PER_TICK,
        "prefix_chunks": PREFIX_CHUNKS, "n_templates": N_TEMPLATES,
        "cache_sets": CACHE_SETS, "max_retries": MAX_RETRIES,
        "throttle_thresh": THROTTLE_THRESH, "defer_max": DEFER_MAX,
        "caps": CAPS,
    }
    res = subprocess.run(
        [sys.executable, "-c", src], capture_output=True, text=True,
        cwd=str(Path(__file__).resolve().parent.parent), timeout=3600)
    assert res.returncode == 0, res.stderr[-3000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def run(force: bool = False, smoke: bool = False):
    ticks = SMOKE_TICKS if smoke else TICKS
    key = "smoke" if smoke else "entries"

    def compute():
        return _sweep(ticks)

    res = cached(f"sharded_bench_{key}", compute, force)
    _emit_bench_json(res, key)
    return res


def _emit_bench_json(res: dict, key: str) -> None:
    doc = {}
    if BENCH_JSON.exists():
        try:
            doc = json.loads(BENCH_JSON.read_text())
        except json.JSONDecodeError:
            doc = {}
    doc["benchmark"] = "sharded_serving"
    doc["config"] = {
        "devices": NDEV, "templates": N_TEMPLATES,
        "prefix_chunks": PREFIX_CHUNKS, "chains_per_tick": CHAINS_PER_TICK,
        "cache_sets": CACHE_SETS, "max_retries": MAX_RETRIES,
        "throttle_thresh": THROTTLE_THRESH, "defer_max": DEFER_MAX,
        "ticks": {"entries": TICKS, "smoke": SMOKE_TICKS},
    }
    doc[key] = res
    BENCH_JSON.write_text(json.dumps(doc, indent=1))


def check(res: dict, committed_doc: dict) -> list[str]:
    """CI gate on the smoke curve: shed rate at cap=2×expected within 1.2×
    of the committed entry, hit ratios bit-stable, fault entries (degrade /
    resize) dropping NOTHING and keeping goodput within 1.2× of committed
    (empty list = pass).

    ``committed_doc`` must be the BENCH_sharded.json content from *before*
    this run (``run`` merges the fresh numbers into the file)."""
    problems = []
    committed = committed_doc.get("smoke", {})
    ref2 = committed.get("2x")
    if ref2 is None:
        problems.append("no committed smoke '2x' entry to compare")
    else:
        got = res.get("2x", {}).get("shed_rate", 1.0)
        budget = ref2["shed_rate"] * 1.2 + 1e-9
        if got > budget:
            problems.append(
                f"2x shed_rate {got:.4f} > committed {ref2['shed_rate']:.4f}"
                f" * 1.2")
    for name, r in res.items():
        ref = committed.get(name)
        if ref is None:
            problems.append(f"{name}: no committed smoke entry")
        elif ref.get("hit_ratio") != r.get("hit_ratio"):
            problems.append(
                f"{name}: hit_ratio {r.get('hit_ratio')} != committed "
                f"{ref.get('hit_ratio')}")
    # the robustness gate: a lost shard or a live resize may cost goodput
    # (sheds, retries, plain fallbacks) but must never drop a request, and
    # the goodput hit must stay within 1.2x of the committed curve
    for name, r in res.items():
        if not r.get("fault"):
            continue
        if r.get("dropped", 1) != 0:
            problems.append(f"{name}: dropped {r['dropped']} requests "
                            "under fault (must be 0)")
        ref = committed.get(name)
        if ref and ref.get("goodput"):
            floor = ref["goodput"] / 1.2 - 1e-9
            if r.get("goodput", 0.0) < floor:
                problems.append(
                    f"{name}: goodput {r.get('goodput', 0.0):.2f} < "
                    f"committed {ref['goodput']:.2f} / 1.2")
    # load-aware placement must not shed MORE than the round-robin deal
    for cap in ("2x", "1x"):
        rr = res.get(f"{cap}-rr", {}).get("shed_rate")
        ld = res.get(cap, {}).get("shed_rate")
        if rr is not None and ld is not None and ld > rr + 1e-9:
            problems.append(
                f"{cap}: load placement shed_rate {ld:.4f} > round-robin "
                f"{rr:.4f}")
    # split placement gate: at equal caps the split entry must at least
    # HALVE the whole-chain fallback rate, match or beat its goodput, and
    # drop nothing — and neither metric may regress vs its own committed
    # entry (fallback_rate within 1.2x, goodput above 1/1.2x)
    for split_name, base_name in (("1x-split", "1x"),
                                  ("2x-deg-split", "2x-deg"),
                                  ("throttle", "1x")):
        sp, base = res.get(split_name), res.get(base_name)
        if sp is None or base is None:
            problems.append(f"{split_name}: missing entry for split gate")
            continue
        if sp.get("dropped", 1) != 0:
            problems.append(f"{split_name}: dropped {sp['dropped']} "
                            "requests (must be 0)")
        if sp["fallback_rate"] > 0.5 * base["fallback_rate"] + 1e-9:
            problems.append(
                f"{split_name}: fallback_rate {sp['fallback_rate']:.4f} > "
                f"0.5 * {base_name} {base['fallback_rate']:.4f}")
        if sp["goodput"] < base["goodput"] - 1e-9:
            problems.append(
                f"{split_name}: goodput {sp['goodput']:.2f} < "
                f"{base_name} {base['goodput']:.2f}")
        ref = committed.get(split_name)
        if ref:
            if sp["fallback_rate"] > ref["fallback_rate"] * 1.2 + 1e-9:
                problems.append(
                    f"{split_name}: fallback_rate {sp['fallback_rate']:.4f}"
                    f" > committed {ref['fallback_rate']:.4f} * 1.2")
            if ref.get("goodput") and sp["goodput"] < ref["goodput"] / 1.2:
                problems.append(
                    f"{split_name}: goodput {sp['goodput']:.2f} < "
                    f"committed {ref['goodput']:.2f} / 1.2")
    return problems


def report(res: dict) -> list[str]:
    lines = [f"sharded serving cap sweep (D={NDEV}, Zipfian templates; "
             "bounded per-peer all_to_all slabs + next-tick retry; "
             "-rr = round-robin chain placement; -split = fragment "
             "packing across slabs; -deg = shard 0 lost at T/4; "
             "-resize = live 8->4 reshard at T/2; throttle = owner-aware "
             "admission deferral)"]
    full = res.get("full", {})
    for name, _cap, _pl, _fault, _thr in CAPS:
        r = res.get(name)
        if not r:
            continue
        loss = (full.get("hit_ratio", 0) - r["hit_ratio"])
        lines.append(
            f"  cap={name:12s} shed={r['shed_rate']:.2%} "
            f"retried={r['retried']} fallbacks={r['fallbacks']} "
            f"dropped={r['dropped']} goodput={r['goodput']:.1f}/tick "
            f"hit_ratio={r['hit_ratio']:.3f} (Δ vs full {loss:+.4f}) "
            f"buf={r['send_buffer_bytes']}B (k={r['k_depth']})")
    for cap in ("2x", "1x"):
        rr, ld = res.get(f"{cap}-rr"), res.get(cap)
        if rr and ld:
            lines.append(
                f"  load-aware placement at {cap}: shed "
                f"{rr['shed_rate']:.2%} -> {ld['shed_rate']:.2%}")
    for split_name, base_name in (("1x-split", "1x"),
                                  ("2x-deg-split", "2x-deg"),
                                  ("throttle", "1x")):
        sp, base = res.get(split_name), res.get(base_name)
        if sp and base:
            lines.append(
                f"  {split_name} vs {base_name}: fallback_rate "
                f"{base['fallback_rate']:.2%} -> {sp['fallback_rate']:.2%}"
                f", goodput {base['goodput']:.1f} -> {sp['goodput']:.1f}"
                f" (split={sp['split_chains']} partial={sp['partial_served']}"
                f" throttled={sp['throttled']})")
    return lines


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny trace (the CI gate block)")
    ap.add_argument("--check", action="store_true",
                    help="recompute the smoke curve and fail on shed-rate "
                         "or hit-ratio regressions vs BENCH_sharded.json")
    args = ap.parse_args()
    committed_doc = (json.loads(BENCH_JSON.read_text())
                     if BENCH_JSON.exists() else {})
    res = run(force=args.force or args.check,
              smoke=args.smoke or args.check)
    print("\n".join(report(res)))
    print(f"merged into {BENCH_JSON}")
    if args.check:
        problems = check(res, committed_doc)
        if problems:
            print("BENCH CHECK FAILED:\n  " + "\n  ".join(problems))
            sys.exit(1)
        print("bench check OK")


if __name__ == "__main__":
    main()
