"""Tiny cells for CPU runs of the harness (the real cells' files, cut)."""

import time

from bench import common, peaks

KV = {"num_sets": 1024, "key_space": 8192, "batch_per_chip": 256,
      "fill_ranks": 8192, "fill_batch": 1024, "sample_uniform_sets": 64,
      "sample_stream_draws": 16}

SERVE = {"smoke": True, "hidden_size": 128, "intermediate_size": 256,
         "num_attention_heads": 4, "num_key_value_heads": 4,
         "num_hidden_layers": 2, "vocab_size": 512,
         "deployment": {"chips": 1, "slots": 4, "max_len": 96,
                        "pool_pages": 24, "page_tokens": 16,
                        "prefix_cache": {"num_sets": 16, "m": 2, "p": 4}}}
CHAT = {"system_prompts": 4, "system_prompt_tokens": 32,
        "user_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 24},
        "output_tokens": {"median": 8, "sigma": 0.5, "min": 4, "max": 24}}


def cell(name: str) -> dict:
    c = common.load_cell(name)
    if c["config_spec"]["system"] == "kv":
        c["config_spec"].update(KV)
    else:
        c["config_spec"].update(SERVE)
        c["traffic_spec"].update(CHAT)
        c["rate_per_s"] = 6.0
    return c


def run(c: dict, seed: int = 2**33 + 17, seconds: float = 1.0, keep=None):
    import jax
    from bench import harness
    peaks.PEAKS.setdefault(jax.devices()[0].device_kind,
                           peaks.PEAKS["TPU v5 lite"])
    return harness.run_cell(c, seed, seconds, False,
                            common.Clock(time.perf_counter()),
                            jax.devices()[:1], keep)
