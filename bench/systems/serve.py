"""An LLM serving deployment under open-loop chat traffic: ``ServeEngine``
with the multi-step-LRU ``PrefixCache`` (on the cache kernel) and the
``PagedKVPool``, run with the engine's own defaults at the sizes the
configuration states, with weights the benchmark draws from the seed.

Set-up makes the weights on the device in one call, builds the engine,
and runs warm-up groups (``bench.generators.chat.warmup``): they cache the
window's system prompts and compile every prefill bucket and message length
the window can reach, and they drain before the window opens.  The window
submits each request when it is due and steps the engine while it holds
work; the client sees a token when the ``step`` that made it returns.

Afterwards a sample of the finished requests, drawn from the seed and
holding the one with the most served tokens, is run through the float32
reference (``bench.reference.phi3``): every served token is compared with
the reference's best at its position.
"""

from __future__ import annotations

import dataclasses
import gc
import time

import numpy as np

from bench import common, counts as counts_mod
from bench.generators import chat
from bench.harness import Check, Outcome
from bench.reference import phi3 as ref

SAMPLE_REQUESTS = 6
# arrivals that ran this late behind their due time mean the generator, not
# the engine, set the pace
STALL_CAP_S = 120.0


def _program_config(spec: dict):
    """The program's config for the arch, with the file's sizes checked
    against it and its depth and positions as the file states them."""
    from repro.configs import get_config
    cfg = get_config(spec["arch"], smoke=spec.get("smoke", False))
    want = {"d_model": spec["hidden_size"], "n_heads": spec["num_attention_heads"],
            "n_kv_heads": spec["num_key_value_heads"],
            "d_ff": spec["intermediate_size"], "vocab_size": spec["vocab_size"],
            "norm_eps": spec["rms_norm_eps"], "rope_theta": spec["rope_theta"],
            "tie_embeddings": spec["tie_word_embeddings"]}
    for k, v in want.items():
        if getattr(cfg, k) != v:
            raise ValueError(f"{spec['arch']}: the program's {k} is "
                             f"{getattr(cfg, k)}, the config file says {v}")
    return dataclasses.replace(cfg, n_layers=spec["num_hidden_layers"])


def make_weights(model, seed: int):
    """Weights in the program's tree and dtypes, drawn on the device in one
    call from the seed: matrices N(0, 1/fan_in), embeddings N(0, 0.02**2),
    norm scales 1 + N(0, 0.1**2)."""
    import jax
    import jax.numpy as jnp
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, sd) in enumerate(leaves):
            name = "/".join(str(getattr(p, "key", p)) for p in path)
            k = jax.random.fold_in(key, i)
            if name.endswith("scale"):
                x = 1.0 + 0.1 * jax.random.normal(k, sd.shape, jnp.float32)
            elif name.endswith(("embed", "lm_head")):
                x = 0.02 * jax.random.normal(k, sd.shape, jnp.float32)
            else:
                x = jax.random.normal(k, sd.shape, jnp.float32) * sd.shape[-2] ** -0.5
            out.append(x.astype(sd.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(common.jax_key(seed, stream=1))


def _build_engine(cfg, spec, params, devices):
    import jax
    from repro.models.model import make_model
    from repro.serving.engine import ServeEngine
    from repro.serving.kv_cache import PagedKVPool
    from repro.serving.prefix_cache import PrefixCache
    dep = spec["deployment"]
    model = make_model(cfg)
    pool = PagedKVPool(cfg, n_pages=dep["pool_pages"],
                       page_tokens=dep["page_tokens"])
    pc_spec = dep["prefix_cache"]
    pc = PrefixCache(num_sets=pc_spec["num_sets"], m=pc_spec["m"],
                     p=pc_spec["p"], chunk_tokens=dep["page_tokens"],
                     use_kernel=devices[0].platform == "tpu")
    return ServeEngine(model, params, slots=dep["slots"],
                       max_len=dep["max_len"], prefix_cache=pc, pool=pool)


def _submit(eng, r):
    from repro.serving.engine import Request
    req = Request(rid=r.rid, prompt=r.prompt, max_new_tokens=r.max_new_tokens)
    eng.submit(req)
    return req


def _drain(eng, reqs, limit_s=600.0):
    t = time.perf_counter()
    while eng.queue or eng.retry_queue or eng.active:
        eng.step()
        if time.perf_counter() - t > limit_s:
            raise RuntimeError("warm-up did not drain")
    return reqs


class _Decode:
    """Work of the decode launches in the window, from the rows that emitted
    a token in each step and their lengths."""

    def __init__(self, sizes):
        self.sizes = sizes
        self.flops = 0.0
        self.bytes = 0.0
        self.launches = 0

    def add(self, live_lens, launches):
        if not launches or not live_lens:
            return
        self.launches += launches
        self.flops += launches * counts_mod.decode_flops(self.sizes, live_lens)
        self.bytes += launches * counts_mod.decode_bytes(self.sizes, live_lens)


def run(ctx) -> Outcome:
    import jax
    spec, traffic, cell = ctx.config, ctx.traffic, ctx.cell
    dep = spec["deployment"]
    cfg = _program_config(spec)
    if chat.max_request_tokens(traffic) > dep["max_len"]:
        raise ValueError("the mix's longest request exceeds max_len")
    with jax.default_device(ctx.devices[0]):
        from repro.models.model import make_model
        params = make_weights(make_model(cfg), ctx.seed)
        eng = _build_engine(cfg, spec, params, ctx.devices)
        jax.block_until_ready(params)
        t = time.perf_counter()
        buckets = [1 << i for i in range(3, 8)]
        groups = chat.warmup(traffic, ctx.seed, cfg.vocab_size, dep["slots"],
                             buckets)
        for g in groups:
            for r in g:
                _submit(eng, r)
            _drain(eng, g)
        common.log(f"[serve] warm-up: {len(groups)} groups, "
                   f"{sum(len(g) for g in groups)} requests in "
                   f"{time.perf_counter() - t:.2f} s")
        out = _window(ctx, eng, traffic, cfg)
    window_reqs, times, decode, stats = out
    device = common.device_stamp(ctx.devices)
    del eng
    gc.collect()

    finished = [r for r in window_reqs if r.out_tokens]
    done = [r for r in window_reqs if r.rid in stats["finished"]]
    sched = {r.rid: r for r in stats["schedule"]}
    ttft = [times[r.rid][0] - sched[r.rid].due_s for r in finished]
    itl = [b - a for r in finished for a, b in zip(times[r.rid],
                                                   times[r.rid][1:])]
    t = time.perf_counter()
    gap, n_tok = _compare(ctx, params, cfg, done)
    common.log(f"[serve] reference over {n_tok} served tokens in "
               f"{time.perf_counter() - t:.2f} s: widest gap {gap:.6g}")

    sizes = counts_mod.dense_decoder_sizes(
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
        cfg.vocab_size, cfg.n_layers)
    skipped = sum(r.prefill_skipped for r in window_reqs)
    counts = {
        "serve_requests": len(window_reqs),
        "serve_prompt_tokens": sum(len(r.prompt) for r in window_reqs),
        "serve_prefill_skipped": skipped,
        "serve_prefill_computed": sum(r.prefill_computed for r in window_reqs),
        "decode_flops": decode.flops,
        "decode_bytes": decode.bytes,
        "decode_launches": decode.launches,
    }
    metrics = {
        "ttft_p90_ms": (common.percentile(ttft, 90) * 1e3, "ms"),
        "itl_p99_ms": (common.percentile(itl, 99) * 1e3, "ms"),
        "setup_s": (ctx.setup_s, "s"),
    }
    failed = len(window_reqs) - len(done)
    checks = {
        # the widest gap a served (greedy) token's reference logit may lie
        # below the reference's best: the cell states it, set from the
        # program's and the fp8 control's readings (PERF.md)
        "served_logit_gap": Check(gap, cell["max_logit_gap"]),
        "served_tokens_compared": Check(n_tok, 1, ">="),
        "requests_unfinished": Check(failed, 0),
    }
    if ctx.keep is not None:
        ctx.keep["ttft_by_due"] = [(sched[r.rid].due_s,
                                    times[r.rid][0] - sched[r.rid].due_s)
                                   for r in finished]
    q = max(1, len(finished) // 4)
    by_due = sorted(finished, key=lambda r: sched[r.rid].due_s)
    head, tail = ([times[r.rid][0] - sched[r.rid].due_s for r in part]
                  for part in (by_due[:q], by_due[-q:]))
    common.log(f"[serve] {len(window_reqs)} requests, {len(done)} finished; "
               f"prefill skipped {skipped}; submissions ran up to "
               f"{stats['late_s']:.4f} s behind due; ttft p50 "
               f"{common.percentile(ttft, 50) * 1e3:.1f} ms, median of the "
               f"first and last quarter {np.median(head) * 1e3:.1f} / "
               f"{np.median(tail) * 1e3:.1f} ms (a growing backlog shows as "
               f"the second far above the first)")
    return Outcome(metrics, counts, checks, attempted=len(window_reqs),
                   failed=failed, device=device)


def _window(ctx, eng, traffic, cfg):
    import jax
    sched = chat.schedule(traffic, ctx.cell["rate_per_s"], ctx.seconds,
                          ctx.seed, cfg.vocab_size)
    sizes = counts_mod.dense_decoder_sizes(
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff,
        cfg.vocab_size, cfg.n_layers)
    decode = _Decode(sizes)
    reqs, times, seen, live = [], {}, {}, []
    late = 0.0
    busy = None
    nxt = 0
    at_open = ctx.open_window()
    with ctx.traced():
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while nxt < len(sched) and sched[nxt].due_s <= now:
                late = max(late, now - sched[nxt].due_s)
                with jax.profiler.TraceAnnotation("submit"):
                    req = _submit(eng, sched[nxt])
                reqs.append(req)
                live.append(req)
                times[req.rid], seen[req.rid] = [], 0
                nxt += 1
            if eng.queue or eng.retry_queue or eng.active:
                if busy is None and ctx.trace:
                    busy = jax.profiler.TraceAnnotation("engine_busy")
                    busy.__enter__()
                launches = eng.decode_launches
                with jax.profiler.TraceAnnotation("step"):
                    eng.step()
                t = time.perf_counter() - t0
                rows = []
                keep = []
                for r in live:
                    n = len(r.out_tokens)
                    if n > seen[r.rid]:
                        times[r.rid].extend([t] * (n - seen[r.rid]))
                        seen[r.rid] = n
                        rows.append(int(eng.cur_len[r.slot]) - 1)
                    if n < r.max_new_tokens:
                        keep.append(r)
                live = keep
                decode.add(rows, eng.decode_launches - launches)
            elif nxt < len(sched):
                if busy is not None:
                    busy.__exit__(None, None, None)
                    busy = None
                wait = sched[nxt].due_s - (time.perf_counter() - t0)
                if wait > 0:
                    with jax.profiler.TraceAnnotation("sleep"):
                        time.sleep(wait)
            else:
                break
            if time.perf_counter() - t0 > ctx.seconds + STALL_CAP_S:
                break
        if busy is not None:
            busy.__exit__(None, None, None)
        ctx.close_window(at_open)
    return reqs, times, decode, {"schedule": sched, "late_s": late,
                                 "finished": {r.rid for r in eng.finished}}


def _compare(ctx, params, cfg, done):
    """The widest reference-logit gap of the served tokens of a sample of
    finished requests: the one with the most served tokens and others drawn
    from the seed.  Returns (gap, tokens compared)."""
    import jax
    import jax.numpy as jnp
    if not done:
        return float("inf"), 0
    rng = np.random.default_rng((ctx.seed, 0xC4))
    longest = max(done, key=lambda r: (len(r.out_tokens), -r.rid))
    others = [r for r in done if r is not longest]
    pick = [longest] + [others[i] for i in rng.permutation(len(others))[
        :SAMPLE_REQUESTS - 1]]
    seq_len = ctx.config["deployment"]["max_len"]
    t_max = ctx.traffic["output_tokens"]["max"]
    tokens = np.zeros((SAMPLE_REQUESTS, seq_len), np.int32)
    positions = np.full((SAMPLE_REQUESTS, t_max), -1, np.int32)
    served = np.full((SAMPLE_REQUESTS, t_max), -1, np.int32)
    for i, r in enumerate(pick):
        seq = np.concatenate([r.prompt, np.asarray(r.out_tokens, np.int32)])
        n = len(r.prompt)
        m = len(r.out_tokens)
        tokens[i, :len(seq) - 1] = seq[:-1]
        positions[i, :m] = np.arange(n - 1, n - 1 + m)
        served[i, :m] = r.out_tokens
    fn = jax.jit(lambda w, t, p, s: ref.gaps(
        w, t, p, s, n_heads=cfg.n_heads, eps=cfg.norm_eps,
        theta=cfg.rope_theta))
    g = np.asarray(fn(params, jnp.asarray(tokens), jnp.asarray(positions),
                      jnp.asarray(served)))
    if ctx.keep is not None:
        ctx.keep.update(params=params, cfg=cfg, tokens=tokens,
                        positions=positions, served=served, gaps=g)
    return float(g.max()), int((positions >= 0).sum())
