"""Plain float32 reference of a Phi-3-mini decoder (arXiv:2404.14219, the
``microsoft/Phi-3-mini-4k-instruct`` config): pre-norm blocks of RMSNorm,
multi-head attention with rotary positions and a causal mask, RMSNorm,
SwiGLU feed-forward; a final RMSNorm and an untied LM head.

Computed in float32 with matmuls at ``highest`` precision, one layer at a
time (a scan over the stacked layer weights), over whole sequences.

Departures from the published model, and why they do not matter here:

* Rotary pairs are (2i, 2i+1), where the published checkpoint rotates
  (i, i + d/2).  The two differ by a fixed permutation of the q and k
  projection columns, so with weights drawn at random they are the same
  model; the weights here are laid out for (2i, 2i+1).
* q, k, v and gate, up are separate matrices where the checkpoint fuses
  them (``qkv_proj``, ``gate_up_proj``): the same products.
* The checkpoint's ``sliding_window`` of 2047 never binds: sequences here
  are at most 512 tokens.

Weights are read by name from the tree the benchmark made them in:
``blocks/{ln1,ln2}/scale``, ``blocks/attn/{wq,wk,wv,wo}`` (d_in, d_out),
``blocks/mlp/{w_gate,w_up,w_down}``, ``head/{embed,lm_head}`` (V, d) and
``head/out_norm/scale``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, theta):
    """x (B, S, H, Dh); rotate pairs (2i, 2i+1) by position * theta**(-2i/Dh)."""
    s, dh = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=F32) / dh)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]       # (S, Dh/2)
    cos = jnp.cos(ang)[None, :, None, :]
    sin = jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def quantize_fp8(w):
    """Weight-only float8 (e4m3) with one scale per matrix: the control."""
    amax = jnp.max(jnp.abs(w))
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def logits(weights, tokens, *, n_heads: int, eps: float, theta: float,
           fp8: bool = False):
    """(B, S, V) float32 logits of every position of ``tokens`` (B, S)."""
    q8 = quantize_fp8 if fp8 else (lambda w: w)
    blocks, head = weights["blocks"], weights["head"]
    b, s = tokens.shape
    h = jnp.take(head["embed"], tokens, axis=0).astype(F32)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(h, w):
        with jax.default_matmul_precision("highest"):
            a = w["attn"]
            x = _rms(h, w["ln1"]["scale"].astype(F32), eps)
            q = x @ q8(a["wq"].astype(F32))
            k = x @ q8(a["wk"].astype(F32))
            v = x @ q8(a["wv"].astype(F32))
            dh = q.shape[-1] // n_heads
            q = _rope(q.reshape(b, s, n_heads, dh), theta)
            k = _rope(k.reshape(b, s, -1, dh), theta)
            v = v.reshape(b, s, -1, dh)
            rep = n_heads // k.shape[2]
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
            sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dh ** -0.5
            sc = jnp.where(causal[None, None], sc, -jnp.inf)
            o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), v)
            h = h + o.reshape(b, s, -1) @ q8(a["wo"].astype(F32))
            m = w["mlp"]
            x = _rms(h, w["ln2"]["scale"].astype(F32), eps)
            g = jax.nn.silu(x @ q8(m["w_gate"].astype(F32)))
            u = x @ q8(m["w_up"].astype(F32))
            h = h + (g * u) @ q8(m["w_down"].astype(F32))
        return h, None

    h, _ = jax.lax.scan(layer, h, blocks)
    with jax.default_matmul_precision("highest"):
        h = _rms(h, head["out_norm"]["scale"].astype(F32), eps)
        return h @ q8(head["lm_head"].astype(F32)).T


def gaps(weights, tokens, positions, served, *, n_heads, eps, theta,
         control: bool = False):
    """For each compared position, how far the served token's reference
    logit lies below the reference's best.  tokens (B, S); positions and
    served (B, T), -1 where nothing is compared.  With ``control`` the
    served tokens are replaced by those the fp8 control puts first at the
    same positions.  Returns (B, T) float32, 0 where nothing is compared."""
    kw = dict(n_heads=n_heads, eps=eps, theta=theta)
    pos = jnp.maximum(positions, 0)
    r = jnp.take_along_axis(logits(weights, tokens, **kw), pos[..., None],
                            axis=1)                             # (B, T, V)
    if control:
        c = jnp.take_along_axis(logits(weights, tokens, fp8=True, **kw),
                                pos[..., None], axis=1)
        served = jnp.argmax(c, -1)
    got = jnp.take_along_axis(r, jnp.maximum(served, 0)[..., None], -1)[..., 0]
    return jnp.where(positions >= 0, jnp.max(r, -1) - got, 0.0)
