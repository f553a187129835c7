"""Pallas paged decode attention: block-table walk over the shared KV pool.

One decode row attends over two segments without ever materializing a
contiguous copy of its sequence:

  1. the *prefix* — ``prefix_len`` tokens resident in the shared
     ``PagedKVPool`` storage ``(n_pages, page_tokens, KVH, Dh)``, reached
     through the row's block table (vLLM-style paged attention: the grid's
     inner dimension walks ``block_table[b, j]`` and the scalar-prefetched
     table drives the BlockSpec index_map, so each step DMAs exactly one
     pool page into VMEM);
  2. the *tail* — the tokens the row computed itself (suffix prefill +
     decoded tokens), stored per-slot at tail position
     ``abs_pos - prefix_len``.

The kernel carries the flash-attention ``(m, l, acc)`` running triple in
f32 VMEM scratch across the sequential inner grid dimension and writes the
normalized context at the final step.  GQA without reshapes across the
tiled axes: the wrapper regroups q to ``(B, rep, KVH, Dh)``, and for each
of the ``rep`` query heads per KV group the scores are a lane reduction of
``q * k`` over Dh on a ``(page_tokens, KVH, Dh)`` block.  Numerics: the
score math of ``models.attention.paged_attn_decode`` (scale in q dtype,
optional tanh softcap, NEG_INF masking), but scores, probabilities and the
context stay f32 where the mirror rounds them to bf16, and accumulation is
flash-ordered, so outputs agree at bf16 resolution (tests gate argmax
equality + allclose against the jnp mirror, which in turn is
bit-identical to the contiguous oracle).

Masked lanes use a *finite* NEG_INF (-1e30), so a block with no valid lane
must not pollute the accumulator: probabilities are explicitly zeroed by
the validity mask rather than relying on ``exp(NEG_INF - m)`` underflow
(which is exp(0)=1 while ``m`` itself still sits at NEG_INF).

Like the msl_cache kernels this runs in interpret mode on CPU so the body
is exercised everywhere; on TPU the same code compiles with the pool in
HBM/ANY and pages streamed per grid step.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _paged_attn_kernel(n_prefix_blocks, n_tail_blocks, page_tokens, softcap,
                       # scalar prefetch
                       bt_ref, plen_ref, cur_ref, wnd_ref,
                       # blocked operands
                       q_ref, pk_ref, pv_ref, tk_ref, tv_ref, out_ref,
                       # scratch
                       m_ref, l_ref, acc_ref):
    b = pl.program_id(0)
    j = pl.program_id(1)
    pt = page_tokens
    rep, kvh = q_ref.shape[1], q_ref.shape[2]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    is_tail = j >= n_prefix_blocks
    plen = plen_ref[b]
    cur = cur_ref[b]
    wnd = wnd_ref[0]

    # both candidate blocks are in VMEM (the pipeline fetched them); pick one
    k_blk = jnp.where(is_tail, tk_ref[0], pk_ref[0]).astype(jnp.float32)
    v_blk = jnp.where(is_tail, tv_ref[0], pv_ref[0]).astype(jnp.float32)

    # token t of the block on the leading axis: (pt, KVH, 1)
    t = jax.lax.broadcasted_iota(jnp.int32, (pt, kvh, 1), 0)
    base = jnp.where(is_tail, plen + (j - n_prefix_blocks) * pt, j * pt)
    pos = base + t                                          # absolute positions
    valid = pos < jnp.where(is_tail, cur + 1, plen)        # no i1 selects
    valid &= (cur - pos < wnd) | (wnd <= 0)

    # One pass per query head of each KV group; q_ref[0, r] holds head
    # g * rep + r for every group g, so scores are a lane reduction of
    # q * k over Dh and never regroup heads across sublanes.
    for r in range(rep):
        q = q_ref[0, r].astype(jnp.float32)                 # (KVH, Dh), pre-scaled
        s = jnp.sum(q[None] * k_blk, axis=-1, keepdims=True)  # (pt, KVH, 1)
        if softcap > 0.0:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_ref[r]                                   # (KVH, 1)
        l_prev = l_ref[r]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=0))
        # NEG_INF is finite: zero masked lanes explicitly (see module docstring)
        p = jnp.where(valid, jnp.exp(s - m_new[None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[r] = alpha * l_prev + jnp.sum(p, axis=0)
        acc_ref[r] = alpha * acc_ref[r] + jnp.sum(p * v_blk, axis=0)
        m_ref[r] = m_new

    @pl.when(j == n_prefix_blocks + n_tail_blocks - 1)
    def _finish():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)                     # dead rows -> 0 out
        out_ref[0] = (acc_ref[...] / l).astype(out_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("softcap", "interpret"))
def paged_attn_decode_call(q, pool_k, pool_v, block_table, tail_k, tail_v,
                           prefix_len, cur_len, *, window=None,
                           softcap: float = 0.0,
                           interpret: bool | None = None):
    """q (B,H,Dh) *unscaled*; pool_k/v (n_pages, pt, KVH, Dh) one layer's
    plane; block_table (B, NP) i32; tail_k/v (B, Tmax, KVH, Dh) with the
    new token already written at ``cur_len - prefix_len``; prefix_len,
    cur_len (B,).  Returns the attention context (B, H, Dh) in q's dtype.

    ``window`` may be None, a python int, or a traced scalar (the per-layer
    sliding window carried through the layer scan); <= 0 means global.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    b, h, dh = q.shape
    n_pages, pt, kvh, _ = pool_k.shape
    npb = block_table.shape[1]
    tmax = tail_k.shape[1]
    ntb = -(-tmax // pt)
    if ntb * pt != tmax:                   # pad tail to page granularity;
        padw = ((0, 0), (0, ntb * pt - tmax), (0, 0), (0, 0))
        tail_k, tail_v = jnp.pad(tail_k, padw), jnp.pad(tail_v, padw)
    scale = jnp.asarray(dh ** -0.5, q.dtype)
    rep = h // kvh
    # (B, H, Dh) -> (B, rep, KVH, Dh): head g * rep + r at [r, g]
    qs = (q * scale).reshape(b, kvh, rep, dh).transpose(0, 2, 1, 3)

    bt = jnp.asarray(block_table, jnp.int32)
    plen = jnp.broadcast_to(jnp.asarray(prefix_len, jnp.int32), (b,))
    cur = jnp.broadcast_to(jnp.asarray(cur_len, jnp.int32), (b,))
    wnd = (jnp.zeros((1,), jnp.int32) if window is None
           else jnp.asarray(window, jnp.int32).reshape(1))

    def q_map(i, j, bt_s, pl_s, cu_s, wd_s):
        return (i, 0, 0, 0)

    def pool_map(i, j, bt_s, pl_s, cu_s, wd_s):
        # prefix steps walk the block table; tail steps park on an
        # arbitrary in-range page (block unused, mask kills its lanes)
        jj = jnp.minimum(j, npb - 1)
        return (bt_s[i, jj], 0, 0, 0)

    def tail_map(i, j, bt_s, pl_s, cu_s, wd_s):
        return (i, jnp.clip(j - npb, 0, ntb - 1), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(b, npb + ntb),
        in_specs=[
            pl.BlockSpec((1, rep, kvh, dh), q_map),
            pl.BlockSpec((1, pt, kvh, dh), pool_map),
            pl.BlockSpec((1, pt, kvh, dh), pool_map),
            pl.BlockSpec((1, pt, kvh, dh), tail_map),
            pl.BlockSpec((1, pt, kvh, dh), tail_map),
        ],
        out_specs=pl.BlockSpec((1, rep, kvh, dh), q_map),
        scratch_shapes=[
            pltpu.VMEM((rep, kvh, 1), jnp.float32),    # running max m
            pltpu.VMEM((rep, kvh, 1), jnp.float32),    # running denom l
            pltpu.VMEM((rep, kvh, dh), jnp.float32),   # unnormalized context
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, npb, ntb, pt, softcap),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, rep, kvh, dh), q.dtype),
        interpret=interpret,
        name="paged_attn_decode_call",
    )(bt, plen, cur, wnd, qs, pool_k, pool_v, tail_k, tail_v)
    return out.transpose(0, 2, 1, 3).reshape(b, h, dh)
