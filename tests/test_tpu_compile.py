"""Compile the main path's kernels and decode step for a described TPU v5e.

Nothing here runs on a chip: each test lowers a kernel (``interpret=False``)
or a jitted step at the widths the chip smoke run uses, for a v5e chip that
is described but not attached, and compiles it with the TPU compiler.  What
the chip's compiler refuses (a layout it cannot lower, VMEM it cannot give,
HBM the program cannot fit) fails here at no chip time.  The topology is
described inside a fixture, so importing this file never loads the TPU
library.
"""

import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.core.multistep import MSLRUConfig

# phase b of chip_smoke.py: 8192-query batches, A = 8 lanes, 2 value planes
BATCH = 8192
CACHE_CFG = MSLRUConfig(num_sets=2**22, m=2, p=4, value_planes=2)
COST_CFG = MSLRUConfig(num_sets=2**22, m=2, p=4, value_planes=2,
                       cost_planes=1)
TABLE_BYTES = CACHE_CFG.num_sets * CACHE_CFG.assoc * CACHE_CFG.planes * 4
V5E_VMEM = 16 * 2**20          # default scoped VMEM limit of a v5e core
V5E_HBM = 16 * 10**9


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a described-chip compile is written to the persistent cache but can
    # never be read back without a chip: keep it out of the cache
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(sharding, shape, dtype=jnp.int32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def _kernel_vmem(compiled) -> int:
    """Scoped VMEM bytes the compiled Pallas kernels use, as their custom
    calls report it (``memory_analysis`` does not count kernel VMEM)."""
    sizes = [int(m) for line in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in line
             for m in re.findall(
                 r'"used_scoped_memory_configs":\[[^\]]*"size":"(\d+)"',
                 line)]
    assert sizes, "no kernel memory config in the compiled HLO"
    return max(sizes)


@pytest.mark.parametrize("block_b", [2048, 256])
@pytest.mark.parametrize("with_ops", [False, True])
def test_access_kernel_compiles(one_chip, block_b, with_ops):
    from repro.kernels.msl_cache import msl_access_kernel_call
    cfg = CACHE_CFG
    a, c = cfg.assoc, cfg.planes
    args = [_spec(one_chip, (BATCH, a, c)),
            _spec(one_chip, (BATCH, cfg.key_planes)),
            _spec(one_chip, (BATCH, cfg.value_planes))]
    if with_ops:
        args.append(_spec(one_chip, (BATCH,)))
    compiled = jax.jit(
        lambda *xs: msl_access_kernel_call(*xs, cfg=cfg, block_b=block_b,
                                           interpret=False)
    ).lower(*args).compile()
    _assert_kernel(compiled)


def _onepass_args(sharding, cfg, block_b, ops, chain, costs):
    nb = BATCH // block_b
    b = BATCH
    args = [_spec(sharding, (cfg.planes, cfg.assoc, b)),
            _spec(sharding, (b, cfg.key_planes)),
            _spec(sharding, (b, cfg.value_planes)),
            _spec(sharding, (b,)) if ops else None,
            _spec(sharding, (b,)), _spec(sharding, (b,)),
            _spec(sharding, (nb,)), _spec(sharding, (nb,)),
            _spec(sharding, (b,)) if chain else None,
            _spec(sharding, (b,)) if costs else None]
    return args


@pytest.mark.parametrize("block_b", [2048, 256])
@pytest.mark.parametrize("variant", ["access", "ops_chain_costs"])
def test_onepass_kernel_compiles(one_chip, block_b, variant):
    from repro.kernels.msl_cache import msl_onepass_kernel_call
    full = variant == "ops_chain_costs"
    cfg = COST_CFG if full else CACHE_CFG
    args = _onepass_args(one_chip, cfg, block_b, full, full, full)
    live = [i for i, x in enumerate(args) if x is not None]

    def run(*xs):
        full_args = [None] * len(args)
        for i, x in zip(live, xs):
            full_args[i] = x
        return msl_onepass_kernel_call(*full_args, cfg=cfg, block_b=block_b,
                                       interpret=False)

    compiled = jax.jit(run).lower(*[args[i] for i in live]).compile()
    _assert_kernel(compiled)
    assert 0 < _kernel_vmem(compiled) < V5E_VMEM


def test_onepass_engine_compiles_at_table_size(one_chip):
    """The whole batched engine (sort, one gather, kernel, one scatter) over
    the phase b table: 2**22 sets, about 400 MB of int32 planes."""
    from repro.core.engine import make_batched_engine
    cfg = CACHE_CFG
    step = make_batched_engine(cfg, engine="onepass", use_kernel=True,
                               interpret=False)
    table = _spec(one_chip, (cfg.num_sets, cfg.assoc, cfg.planes))
    keys = _spec(one_chip, (BATCH, cfg.key_planes))
    vals = _spec(one_chip, (BATCH, cfg.value_planes))
    compiled = jax.jit(step).lower(table, keys, vals).compile()
    _assert_kernel(compiled)
    # the table stays in its compact layout: no padded re-layout copy
    assert compiled.memory_analysis().temp_size_in_bytes < TABLE_BYTES // 4


def test_sequential_oracle_compiles_at_table_size(one_chip):
    """The oracle phase b checks against: its scan carries the table in a
    row layout (one padded copy, about 2 GB), which must still leave the
    chip room for the engine's own table."""
    from repro.core.engine import make_sequential_engine
    cfg = CACHE_CFG
    seq = make_sequential_engine(cfg)
    compiled = jax.jit(seq).lower(
        _spec(one_chip, (cfg.num_sets, cfg.assoc, cfg.planes)),
        _spec(one_chip, (BATCH, cfg.key_planes)),
        _spec(one_chip, (BATCH, cfg.value_planes))).compile()
    mem = compiled.memory_analysis()
    assert (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes) < V5E_HBM // 4


# phase c of chip_smoke.py: phi3-mini at its published widths, 4 slots,
# max_len 1024, a pool of 256 pages of 16 tokens
SLOTS, MAX_LEN, PAGES, PAGE_TOKENS = 4, 1024, 256, 16


def _phi3(n_layers):
    import dataclasses
    from repro.configs import get_config
    return dataclasses.replace(get_config("phi3-mini-3.8b"),
                               n_layers=n_layers)


def test_paged_attn_kernel_compiles_phi3(one_chip):
    from repro.kernels.paged_attn import paged_attn_decode_call
    cfg = _phi3(1)
    h, kvh, dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    bf16 = jnp.bfloat16
    args = (_spec(one_chip, (SLOTS, h, dh), bf16),
            _spec(one_chip, (PAGES, PAGE_TOKENS, kvh, dh), bf16),
            _spec(one_chip, (PAGES, PAGE_TOKENS, kvh, dh), bf16),
            _spec(one_chip, (SLOTS, MAX_LEN // PAGE_TOKENS)),
            _spec(one_chip, (SLOTS, MAX_LEN, kvh, dh), bf16),
            _spec(one_chip, (SLOTS, MAX_LEN, kvh, dh), bf16),
            _spec(one_chip, (SLOTS,)), _spec(one_chip, (SLOTS,)))
    compiled = jax.jit(
        lambda *xs: paged_attn_decode_call(*xs, interpret=False)
    ).lower(*args).compile()
    _assert_kernel(compiled)


def test_phi3_decode_step_compiles(one_chip):
    """phi3-mini ``decode_step`` at full width, depth cut to 2."""
    from repro.models.model import make_model
    model = make_model(_phi3(2))
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache = jax.eval_shape(lambda: model.init_cache(SLOTS, MAX_LEN))

    def place(tree):
        return jax.tree.map(
            lambda x: _spec(one_chip, x.shape, x.dtype), tree)

    compiled = jax.jit(model.decode_step).lower(
        place(params), _spec(one_chip, (SLOTS, 1)), place(cache),
        _spec(one_chip, (SLOTS,))).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes < V5E_HBM
