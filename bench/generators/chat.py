"""Chat traffic over shared system prompts, open loop.

A request is a system prompt (one of ``system_prompts`` templates of
``system_prompt_tokens`` tokens, picked with Zipf(``prompt_popularity_alpha``)
popularity) followed by a user message, and asks for an output whose length
is drawn like the message's: lognormal around ``median`` with ``sigma``,
clipped to [``min``, ``max``].  Arrivals are Poisson at the cell's rate.

Every seed gets the same work in another order.  The multiset of request
shapes (template, message length, output length) and of inter-arrival gaps
is drawn once from ``shape_seed``: ``n = round(rate * seconds)`` arrival
times uniform on the window (a Poisson process given its count).  The run's
seed permutes the shapes and, separately, the gaps, and draws every token.
So runs differ in order and content, not in how much they ask.
"""

from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class ChatRequest:
    rid: int
    due_s: float                 # seconds after the window opens
    template: int
    prompt: np.ndarray           # int32 tokens, template then user message
    max_new_tokens: int


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _zipf_picks(rng, n_items: int, alpha: float, n: int) -> np.ndarray:
    w = 1.0 / np.arange(1, n_items + 1) ** alpha
    return rng.choice(n_items, size=n, p=w / w.sum())


def templates(traffic: dict, seed: int, vocab: int) -> list[np.ndarray]:
    rng = np.random.default_rng((seed, 0x7E))
    return [rng.integers(1, vocab, traffic["system_prompt_tokens"]
                         ).astype(np.int32)
            for _ in range(traffic["system_prompts"])]


def shapes(traffic: dict, n: int):
    """The seed-independent multiset: (gaps s, templates, user lens, out lens)
    for ``n`` requests over a window of one second (gaps scale with it)."""
    rng = np.random.default_rng(traffic["shape_seed"])
    arrivals = np.sort(rng.random(n))
    gaps = np.diff(np.concatenate([[0.0], arrivals]))
    tpl = _zipf_picks(rng, traffic["system_prompts"],
                      traffic["prompt_popularity_alpha"], n)
    user = _lengths(rng, traffic["user_tokens"], n)
    out = _lengths(rng, traffic["output_tokens"], n)
    return gaps, tpl, user, out


def schedule(traffic: dict, rate: float, seconds: float, seed: int,
             vocab: int) -> list[ChatRequest]:
    """The window's requests, in due order."""
    n = max(1, int(round(rate * seconds)))
    gaps, tpl, user, out = shapes(traffic, n)
    rng = np.random.default_rng((seed, 0xA5))
    due = np.cumsum(gaps[rng.permutation(n)]) * seconds
    order = rng.permutation(n)
    temps = templates(traffic, seed, vocab)
    reqs = []
    for j in range(n):
        k = order[j]
        trng = np.random.default_rng((seed, 0xC0, j))
        msg = trng.integers(1, vocab, int(user[k])).astype(np.int32)
        reqs.append(ChatRequest(j, float(due[j]), int(tpl[k]),
                                np.concatenate([temps[tpl[k]], msg]),
                                int(out[k])))
    return reqs


def max_request_tokens(traffic: dict) -> int:
    return (traffic["system_prompt_tokens"] + traffic["user_tokens"]["max"]
            + traffic["output_tokens"]["max"])


def warmup(traffic: dict, seed: int, vocab: int, slots: int,
           buckets: list[int]) -> list[list[ChatRequest]]:
    """Groups of requests, each group submitted at once and drained before
    the next, that make the engine compile what the window can reach.

    A tick admits up to ``slots`` requests and prefills them in one wave
    padded to a power-of-two batch and to the message bucket of its longest
    message; the engine then slices each row's own length out of the wave.
    So the groups are: one request per template (every template cached);
    then, for each wave size, every message length under every bucket, with
    the bucket's longest message leading each group so the wave pads to it.
    Templates are the window's; messages come from another stream."""
    temps = templates(traffic, seed, vocab)
    rng = np.random.default_rng((seed, 0x3A))
    lo, hi = traffic["user_tokens"]["min"], traffic["user_tokens"]["max"]
    groups: list[list[int]] = [[lo] for _ in temps]
    seeded = len(groups)
    sizes = [1 << i for i in range(slots.bit_length()) if 1 << i <= slots]
    prev = lo - 1
    for b in buckets:
        top = min(b, hi)
        if top < lo:
            continue
        own = list(range(max(prev + 1, lo), top + 1))   # pad to this bucket
        under = list(range(lo, top + 1))                # fit under it
        prev = b
        groups += [[u] for u in own]
        for bp in sizes[1:]:
            for i in range(0, len(under), bp - 1):
                part = under[i:i + bp - 1]
                part += under[:bp - 1 - len(part)]      # a full wave
                groups.append([top] + part)
    out, rid = [], 0
    for j, g in enumerate(groups):
        reqs = []
        for u in g:
            t = j if j < seeded else int(rng.integers(0, len(temps)))
            msg = rng.integers(1, vocab, u).astype(np.int32)
            reqs.append(ChatRequest(-1 - rid, 0.0, t,
                                    np.concatenate([temps[t], msg]), 2))
            rid += 1
        out.append(reqs)
    return out
