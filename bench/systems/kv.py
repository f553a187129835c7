"""A look-aside key-value cache under YCSB traffic: the multi-step LRU table
on one chip (``MultiStepLRUCache``, one-pass engine, Pallas kernel) or
sharded over the chips of the cell (``make_sharded_engine``, all_to_all
route).

Set-up fills the table with the stream's most popular keys through the
program's own batched engine, least popular first, then warms the window's
programs with batches of another stream.  The window is a closed loop: one
client keeps ``in_flight`` batches dispatched, and an operation counts once
its batch's results are on the host.  Afterwards every operation of a
sample of sets, drawn from the seed, is replayed by the plain reference
from the sample's rows before the window, and its answers and final rows
are compared with the program's.
"""

from __future__ import annotations

import collections
import gc
import time

import numpy as np

from bench import common, kv_ring
from bench.counts import kv_row_bytes
from bench.generators import ycsb
from bench.harness import Check, Outcome
from bench.reference import kvcache as ref

# answers a replayed sample must hold before a run can be called correct
MIN_CHECKED = 1000
# the most operations a second a window may answer on each chip: its host
# buffers are sized from this before it opens, and a run that dispatches
# more batches fails.  Twice the one-chip cell's rate (2.8 M, the host
# setting the pace), and one and a half times what its engine's device time
# alone would allow (4.0 M).
MAX_OPS_PER_S_PER_CHIP = 6_000_000


class _Engine:
    """The program under test behind one call: ``dispatch`` starts a batch
    and returns its device result; ``fetch`` brings one to the host."""

    def __init__(self, spec: dict, devices):
        import jax
        from repro.core import OP_ACCESS, OP_DELETE, MSLRUConfig
        self.cfg = MSLRUConfig(num_sets=spec["num_sets"], m=spec["m"],
                               p=spec["p"], key_planes=1,
                               value_planes=spec["value_planes"])
        self.ops_map = np.array([OP_ACCESS, OP_DELETE], np.int32)
        self.chips = len(devices)
        if self.chips == 1:
            from repro.core import MultiStepLRUCache
            with jax.default_device(devices[0]):
                self.cache = MultiStepLRUCache(self.cfg, engine="onepass",
                                               use_kernel=True)
            self.sharding = jax.sharding.SingleDeviceSharding(devices[0])
        else:
            from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
            from repro.core.multistep import init_table
            from repro.core.sharded import make_sharded_engine
            mesh = Mesh(np.array(devices), ("cache",))
            self.sharding = NamedSharding(mesh, P("cache"))
            self.run = make_sharded_engine(self.cfg, mesh, cap="full",
                                           engine="onepass", use_kernel=True)
            self.table = jax.jit(lambda: init_table(self.cfg),
                                 out_shardings=NamedSharding(
                                     mesh, P("cache", None, None)))()

    def put(self, x):
        """``x`` (an array or a tuple of them) on the engine's devices."""
        import jax
        return jax.device_put(x, self.sharding)

    def dispatch(self, keys, vals, ops=None):
        """keys (B,) int32, vals (B, V) int32, ops (B,) program op codes or
        None (all get-or-fill), each on the device already."""
        if self.chips == 1:
            return self.cache.access(keys, vals, ops)
        self.table, hit, val, served = self.run(self.table, keys[:, None],
                                                vals, ops)
        return {"hit": hit, "value": val, "served": served}

    def fetch(self, res) -> dict:
        import jax
        if self.chips == 1:
            host = jax.device_get(res)
            return {"hit": host.hit, "value": host.value, "pos": host.pos,
                    "evicted_key": host.evicted_key,
                    "evicted_value": host.evicted_val,
                    "evicted_valid": host.evicted_valid}
        return jax.device_get(res)

    @property
    def table_now(self):
        return self.cache.table if self.chips == 1 else self.table


def _sample_rows(table, sets: np.ndarray):
    """The rows of ``sets`` as (n, A, C) int32 on the host.  Reads set
    columns of the table's (C*A, S) view, the table's own HBM layout."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def take(t, idx):
        s, a, c = t.shape
        view = jnp.transpose(t, (2, 1, 0)).reshape(c * a, s)
        cols = jnp.take(view, idx, axis=1)             # (C*A, n)
        return jnp.transpose(cols.reshape(c, a, -1), (2, 1, 0))

    return np.asarray(take(table, jnp.asarray(sets, jnp.int32)))


def _rows_dict(rows: np.ndarray, sets: np.ndarray) -> dict:
    return {int(s): [(int(r[a, 0]), tuple(r[a, 1:])) for a in range(r.shape[0])]
            for s, r in zip(sets, rows)}


def _fill(eng: _Engine, spec: dict, key_space: int):
    """The stream's ``fill_ranks`` most popular keys, least popular first,
    through the program's batched engine in batches of ``fill_batch`` per
    chip; ACCESS-only, as a warm cache would have taken them."""
    import jax
    import jax.numpy as jnp
    n = int(spec["fill_ranks"])
    b = int(spec["fill_batch"]) * eng.chips
    calls = -(-n // b)

    def gen(j):
        ranks = (calls * b - 1 - j * b - jnp.arange(b, dtype=jnp.int32))
        ranks = jnp.maximum(ranks, 0).astype(jnp.uint32)
        keys = ycsb.popular_keys(ranks, key_space)
        return keys, ycsb.record_values_device(keys)

    gen_s = jax.jit(gen, out_shardings=(eng.sharding, eng.sharding))
    res = None
    for j in range(calls):
        keys, vals = gen_s(jnp.int32(j))
        res = eng.dispatch(keys, vals)
    jax.block_until_ready((res, eng.table_now))
    return calls


def _sample_sets(spec, traffic, stream: ycsb.Stream, seed: int) -> np.ndarray:
    """Sets to replay, from the seed: the sets of the hottest zipfian ranks,
    the sets of keys at random positions of the window's first batches,
    and sets drawn uniformly."""
    s = spec["num_sets"]
    rng = np.random.default_rng((seed, 0x5E7))
    picks = []
    if traffic["distribution"] == "zipfian":
        hot = ycsb.popular_keys_host(np.arange(spec["sample_hot_ranks"]),
                                     stream.key_space)
        picks.append(ref.fmix32_sets(hot, s))
    draws = spec["sample_stream_draws"]
    first = int(spec["sample_from_batches"])
    for i in sorted(set(rng.integers(0, first, draws).tolist())):
        keys, _ = stream.batch(i)
        k = keys[rng.integers(0, keys.size, max(1, draws // first))]
        picks.append(ref.fmix32_sets(k, s))
    picks.append(rng.integers(0, s, spec["sample_uniform_sets"]))
    return np.unique(np.concatenate(picks)).astype(np.int64)


def run(ctx) -> Outcome:
    spec, traffic = ctx.config, ctx.traffic
    key_space = int(spec["key_space"])
    eng = _Engine(spec, ctx.devices)
    batch = int(spec["batch_per_chip"]) * eng.chips
    in_flight = int(traffic["in_flight"])
    stream = ycsb.Stream(traffic, key_space, batch, ctx.seed)

    t = time.perf_counter()
    calls = _fill(eng, spec, key_space)
    common.log(f"[kv] {eng.cfg.num_sets} sets x {eng.cfg.assoc} lanes on "
               f"{eng.chips} chip(s); filled with {calls} calls in "
               f"{time.perf_counter() - t:.3f} s")

    # the window's batches come from a child process, through shared memory
    ring = kv_ring.BatchRing(traffic, key_space, batch, ctx.seed,
                             eng.ops_map)
    try:
        # warm the window's programs on batches of another stream
        warm = ycsb.Stream(traffic, key_space, batch, ctx.seed + 1)
        for i in range(2):
            host = eng.fetch(eng.dispatch(*eng.put(
                kv_ring.make_batch(warm, i, eng.ops_map))))
        sets = _sample_sets(spec, traffic, stream, ctx.seed)
        rows_before = _sample_rows(eng.table_now, sets)
        max_batches = -(-int(ctx.seconds * MAX_OPS_PER_S_PER_CHIP
                             * eng.chips) // batch) + in_flight
        win = _Window(max_batches, batch, host)
        ring.wait_full()
        stats = _window(ctx, eng, ring, win, in_flight)
    finally:
        ring.close()
    n, completed = stats["dispatched"], stats["completed"]
    update_code = eng.ops_map[ycsb.UPDATE]

    device = common.device_stamp(ctx.devices)
    rows_after = _sample_rows(eng.table_now, sets)
    del eng
    gc.collect()

    keys = win.keys[:n].reshape(-1)
    ops = np.where(win.ops[:n].reshape(-1) == update_code, ycsb.UPDATE,
                   ycsb.READ).astype(np.int8)
    fields = {f: a[:n].reshape((n * batch,) + a.shape[2:])
              for f, a in win.answers.items() if f != "served"}
    unserved = (int((~win.answers["served"][:n]).sum())
                if "served" in win.answers else 0)
    t = time.perf_counter()
    key_sets = ref.fmix32_sets(keys, spec["num_sets"])
    bad, checked, final = ref.replay(
        _rows_dict(rows_before, sets), spec["p"], keys,
        ycsb.record_values(keys), ops, key_sets, fields)
    rows_bad = ref.rows_differ(final, _rows_dict(rows_after, sets))
    if ctx.keep is not None:
        ctx.keep.update(rows_before=_rows_dict(rows_before, sets), p=spec["p"],
                        keys=keys, ops=ops, sets=key_sets, final=final,
                        answers=fields)
    _log_window(stats, batch, ctx.seconds, win)
    common.log(f"[kv] reference replayed {checked} operations of {len(sets)} "
               f"sets in {time.perf_counter() - t:.2f} s")

    ops_done = completed * batch
    latency = win.latency[:completed]
    per_batch_sets = [np.unique(key_sets[j * batch:(j + 1) * batch]).size
                      for j in range(0, n, max(1, n // 64))]
    counts = {
        "kv_batches": n,
        "kv_batch_queries": batch,
        "kv_distinct_sets_mean": float(np.mean(per_batch_sets)),
        "kv_row_bytes": kv_row_bytes(spec["m"] * spec["p"],
                                     1 + spec["value_planes"]),
        "kv_query_bytes": 4 + 4 * spec["value_planes"] + 4,
        "kv_result_bytes": _result_bytes(spec, len(ctx.devices)),
        "chips": len(ctx.devices),
    }
    metrics = {
        "kv_ops_per_s": (ops_done / ctx.seconds, "ops/s"),
        "kv_p99_ms": (common.percentile(latency, 99) * 1e3
                      if latency.size else float("inf"), "ms"),
        "setup_s": (ctx.setup_s, "s"),
    }
    checks = {
        "kv_answers_wrong": Check(bad, 0),
        "kv_rows_wrong": Check(rows_bad, 0),
        "kv_ops_shed": Check(unserved, 0),
        "kv_answers_checked": Check(checked, MIN_CHECKED, ">="),
    }
    return Outcome(metrics, counts, checks, attempted=n * batch,
                   failed=unserved, device=device)


class _Window:
    """Host memory for what the window keeps, allocated and written once
    before it opens, so the timed loop only copies into it: each batch's
    keys and op codes and its answers (``template`` is one batch's), and
    each answered batch's latency and completion time."""

    def __init__(self, max_batches: int, batch: int, template: dict):
        self.max_batches = max_batches
        self.keys = _touched((max_batches, batch), np.int32)
        self.ops = _touched((max_batches, batch), np.int32)
        self.answers = {f: _touched((max_batches,) + np.shape(a),
                                    np.asarray(a).dtype)
                        for f, a in template.items()}
        self.latency = _touched((max_batches,), np.float64)
        self.done_at = _touched((max_batches,), np.float64)

    def keep(self, j: int, keys, ops, host: dict):
        self.keys[j] = keys
        self.ops[j] = ops
        for f, buf in self.answers.items():
            buf[j] = host[f]


def _touched(shape, dtype) -> np.ndarray:
    a = np.empty(shape, dtype)
    a.fill(0)                 # every page is mapped now, not in the window
    return a


def _window(ctx, eng: _Engine, ring, win: _Window, in_flight: int) -> dict:
    """The closed loop: keeps ``in_flight`` batches dispatched; a batch
    counts once its results are on the host.  Batches dispatched before the
    window closes are all kept, also those answered after it."""
    import jax
    pending = collections.deque()
    n = completed = 0
    gen_wait = dispatch_s = fetch_s = 0.0
    host_before = _host_counters()
    at_open = ctx.open_window()
    with ctx.traced():
        t_close = time.perf_counter() + ctx.seconds
        while True:
            while len(pending) < in_flight and time.perf_counter() < t_close:
                if n == win.max_batches:
                    raise RuntimeError(
                        f"the window dispatched {n} batches, the most its "
                        f"buffers hold ({MAX_OPS_PER_S_PER_CHIP} ops/s a "
                        f"chip); raise MAX_OPS_PER_S_PER_CHIP")
                tw = time.perf_counter()
                with jax.profiler.TraceAnnotation("gen_wait"):
                    keys, vals, ops = ring.take(n)
                t_d = time.perf_counter()
                gen_wait += t_d - tw
                with jax.profiler.TraceAnnotation("dispatch"):
                    res = eng.dispatch(*eng.put((keys, vals, ops)))
                dispatch_s += time.perf_counter() - t_d
                pending.append((n, t_d, res, keys, ops))
                n += 1
            if not pending:
                break
            j, t_d, res, keys, ops = pending.popleft()
            t_f = time.perf_counter()
            with jax.profiler.TraceAnnotation("fetch"):
                host = eng.fetch(res)
            t_r = time.perf_counter()
            fetch_s += t_r - t_f
            win.keep(j, keys, ops, host)
            ring.release()
            if t_r <= t_close:
                win.latency[completed] = t_r - t_d
                win.done_at[completed] = t_r
                completed += 1
        ctx.close_window(at_open)
    steal, switches = (b - a for a, b in zip(host_before, _host_counters()))
    return {"dispatched": n, "completed": completed, "gen_wait": gen_wait,
            "dispatch_s": dispatch_s, "fetch_s": fetch_s, "steal_s": steal,
            "switches": switches}


def _host_counters() -> tuple:
    """(CPU time the hypervisor took from this machine's CPUs, summed, s;
    involuntary context switches of this process): what the host took
    from the run."""
    import os
    import resource
    try:
        with open("/proc/stat") as f:
            steal = int(f.readline().split()[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        steal = float("nan")
    return steal, resource.getrusage(resource.RUSAGE_SELF).ru_nivcsw


def _log_window(stats: dict, batch: int, seconds: float, win: _Window):
    """The window's end-to-end numbers with what explains their spread:
    the generator's wait, the host's time in dispatch and fetch, the gaps
    between successive completions (the longest with their offsets), and
    what the host took from the run."""
    completed = stats["completed"]
    latency = win.latency[:completed]
    done_at = win.done_at[:completed]
    gaps = np.diff(done_at) * 1e3 if completed > 1 else np.zeros(1)
    p99 = common.percentile(latency, 99) * 1e3 if completed else float("inf")
    common.log(f"[kv] window: kv_ops_per_s {completed * batch / seconds} "
               f"kv_p99_ms {p99}; {stats['dispatched']} batches dispatched, "
               f"{completed} answered in {seconds} s; generator waited "
               f"{stats['gen_wait']:.4f} s, dispatch {stats['dispatch_s']:.4f}"
               f" s, fetch {stats['fetch_s']:.4f} s; completion gaps ms p50 "
               f"{common.percentile(gaps, 50):.4f} p99 "
               f"{common.percentile(gaps, 99):.4f} max {gaps.max():.4f}, "
               f"{int((gaps > 50).sum())} over 50 ms; CPU steal "
               f"{stats['steal_s']:.2f} s (all CPUs), {stats['switches']} "
               f"involuntary switches")
    long = [f"{done_at[j] - done_at[0]:.3f}s:{gaps[j]:.1f}ms"
            for j in np.flatnonzero(gaps > 50)[:10]]
    if long:
        common.log(f"[kv] window: gaps over 50 ms (at, length) "
                   f"{' '.join(long)}")


def _result_bytes(spec, chips: int) -> int:
    """Bytes of one query's answer as the path returns it."""
    v = spec["value_planes"]
    if chips == 1:
        return 1 + 4 * v + 4 + 4 + 4 * v + 1
    return 1 + 4 * v + 1
