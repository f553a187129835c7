"""Work a step needs, from its shapes alone: the bytes a cache batch must
move and the FLOPs and bytes of a decode launch.  The same count holds for
any implementation, so a faster layout shows as a higher roofline share."""

from __future__ import annotations


def kv_batch_bytes(distinct_sets: float, queries: int, row_bytes: int,
                   query_bytes: int, result_bytes: int) -> float:
    """One row read and one written per distinct set, each query's key,
    value and op in, and its result out."""
    return 2.0 * distinct_sets * row_bytes + queries * (query_bytes
                                                       + result_bytes)


def kv_row_bytes(assoc: int, planes: int) -> int:
    return assoc * planes * 4


def dense_decoder_sizes(d: int, n_heads: int, n_kv_heads: int, d_head: int,
                        d_ff: int, vocab: int, n_layers: int) -> dict:
    """Parameters of a pre-norm decoder with gated FFN and untied head."""
    attn = d * n_heads * d_head * 2 + d * n_kv_heads * d_head * 2
    ffn = 3 * d * d_ff
    return {"layer_matmul": attn + ffn, "head": vocab * d,
            "norms": (2 * n_layers + 1) * d, "layers": n_layers,
            "kv_row": 2 * n_kv_heads * d_head, "q_width": n_heads * d_head,
            "embed_row": d}


def decode_flops(sizes: dict, live_lens) -> float:
    """Model FLOPs of one decode launch: for each active row, every matmul
    weight once (2 FLOPs a weight), the LM head, and attention over its
    ``len + 1`` keys (scores and weighted sum, per layer)."""
    per_row = 2.0 * (sizes["layers"] * sizes["layer_matmul"] + sizes["head"])
    attn = sum(4.0 * sizes["q_width"] * (n + 1) for n in live_lens)
    return len(live_lens) * per_row + sizes["layers"] * attn


def decode_bytes(sizes: dict, live_lens, weight_bytes: int = 2,
                 kv_bytes: int = 2, norm_bytes: int = 4) -> float:
    """Bytes one decode launch needs: every weight read once, each active
    row's live KV read and its new KV row written, one embedding row read
    per active row."""
    weights = (sizes["layers"] * sizes["layer_matmul"] + sizes["head"]) * weight_bytes
    weights += sizes["norms"] * norm_bytes
    kv = sum(sizes["layers"] * sizes["kv_row"] * kv_bytes * (n + 1)
             for n in live_lens)
    embed = len(live_lens) * sizes["embed_row"] * weight_bytes
    return float(weights + kv + embed)
