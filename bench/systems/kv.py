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

import gc
import queue
import threading
import time

import numpy as np

from bench import common
from bench.counts import kv_row_bytes
from bench.generators import ycsb
from bench.harness import Check, Outcome
from bench.reference import kvcache as ref

# answers a replayed sample must hold before a run can be called correct
MIN_CHECKED = 1000


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
    import jax
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

    def device_batch(keys, ops):
        vals = ycsb.record_values(keys)
        return (eng.put(keys), eng.put(vals),
                eng.put(eng.ops_map[ops.astype(np.int64)]))

    # warm the window's programs on batches of another stream
    warm = ycsb.Stream(traffic, key_space, batch, ctx.seed + 1)
    for i in range(2):
        eng.fetch(eng.dispatch(*device_batch(*warm.batch(i))))
    sets = _sample_sets(spec, traffic, stream, ctx.seed)
    rows_before = _sample_rows(eng.table_now, sets)

    # the generator runs ahead on a thread of its own
    q: queue.Queue = queue.Queue(maxsize=4 * in_flight)
    stop = threading.Event()

    def produce():
        i = 0
        while not stop.is_set():
            item = (i,) + stream.batch(i)
            while not stop.is_set():
                try:
                    q.put(item, timeout=0.05)
                    break
                except queue.Full:
                    continue
            i += 1

    producer = threading.Thread(target=produce, daemon=True)
    producer.start()
    while q.qsize() < q.maxsize:
        time.sleep(0.001)

    batches, answers, latency = [], [], []
    gen_wait = 0.0
    completed = 0
    pending = []
    at_open = ctx.open_window()
    with ctx.traced():
        t_open = time.perf_counter()
        t_close = t_open + ctx.seconds
        while True:
            while len(pending) < in_flight and time.perf_counter() < t_close:
                tw = time.perf_counter()
                with jax.profiler.TraceAnnotation("gen_wait"):
                    _, keys, ops = q.get()
                gen_wait += time.perf_counter() - tw
                with jax.profiler.TraceAnnotation("dispatch"):
                    t_d = time.perf_counter()
                    res = eng.dispatch(*device_batch(keys, ops))
                pending.append((t_d, res))
                batches.append((keys, ops))
            if not pending:
                break
            t_d, res = pending.pop(0)
            with jax.profiler.TraceAnnotation("fetch"):
                host = eng.fetch(res)
            t_r = time.perf_counter()
            answers.append(host)
            if t_r <= t_close:
                completed += 1
                latency.append(t_r - t_d)
        ctx.close_window(at_open)
    stop.set()
    producer.join(timeout=10)

    device = common.device_stamp(ctx.devices)
    rows_after = _sample_rows(eng.table_now, sets)
    del eng
    gc.collect()

    keys = np.concatenate([b[0] for b in batches])
    ops = np.concatenate([b[1] for b in batches])
    fields = {f: np.concatenate([a[f] for a in answers])
              for f in answers[0] if f != "served"}
    unserved = sum(int((~a["served"]).sum()) for a in answers
                   if "served" in a)
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
    common.log(f"[kv] window: {len(batches)} batches dispatched, "
               f"{completed} answered in {ctx.seconds} s; generator waited "
               f"{gen_wait:.4f} s; reference replayed {checked} operations "
               f"of {len(sets)} sets in {time.perf_counter() - t:.2f} s")

    ops_done = completed * batch
    per_batch_sets = [np.unique(key_sets[j * batch:(j + 1) * batch]).size
                      for j in range(0, len(batches), max(1, len(batches) // 64))]
    counts = {
        "kv_batches": len(batches),
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
                      if latency else float("inf"), "ms"),
        "setup_s": (ctx.setup_s, "s"),
    }
    checks = {
        "kv_answers_wrong": Check(bad, 0),
        "kv_rows_wrong": Check(rows_bad, 0),
        "kv_ops_shed": Check(unserved, 0),
        "kv_answers_checked": Check(checked, MIN_CHECKED, ">="),
    }
    return Outcome(metrics, counts, checks, attempted=len(batches) * batch,
                   failed=unserved, device=device)


def _result_bytes(spec, chips: int) -> int:
    """Bytes of one query's answer as the path returns it."""
    v = spec["value_planes"]
    if chips == 1:
        return 1 + 4 * v + 4 + 4 + 4 * v + 1
    return 1 + 4 * v + 1
