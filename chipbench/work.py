"""Operations and bytes the algorithm needs, computed from shapes.

Counts are the algorithm's, not the implementation's: live keys and
values only (no padded pool, no padded head dimension), matmuls counted
once (no recompute), the vocabulary head only where it is computed.
``m`` is the model block of a configuration file (see ``configs/``).
A multiply-add counts as two operations; weights, activations and the
KV cache are bfloat16 (2 bytes) as served.
"""
from __future__ import annotations

from typing import Dict

BYTES = 2


def layer_matmul_flops(m: Dict) -> int:
    """Projection and MLP operations of one layer for one token."""
    d, f = m["d_model"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    return 2 * (d * H * hd + 2 * d * KV * hd + H * hd * d + 3 * d * f)


def head_flops(m: Dict) -> int:
    """The vocabulary head for one position (the published vocabulary;
    the rows the program pads it with are not the algorithm's)."""
    return 2 * m["d_model"] * m["vocab"]


def attn_flops(m: Dict, n_queries: int, kv_end: int) -> int:
    """Causal attention of queries at positions ``kv_end - n_queries ..
    kv_end - 1`` over every earlier key, all layers: QK^T and PV, two
    operations per multiply-add each, so 4 * heads * head_dim per
    (query, visible key) pair."""
    first = kv_end - n_queries
    pairs = n_queries * first + n_queries * (n_queries + 1) // 2
    return 4 * m["n_heads"] * m["head_dim"] * pairs * m["n_layers"]


def attn_bytes(m: Dict, n_queries: int, kv_end: int) -> int:
    """One attention call, all layers: read the queries and the ``kv_end``
    live keys and values, write the outputs."""
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    per_layer = (2 * n_queries * H * hd + 2 * kv_end * KV * hd) * BYTES
    return per_layer * m["n_layers"]


def decode_call(m: Dict, kv_len: int) -> Dict[str, int]:
    """One paged-decode attention call for one sequence whose new token
    is key ``kv_len - 1``."""
    return {"flops": attn_flops(m, 1, kv_len),
            "bytes": attn_bytes(m, 1, kv_len)}


def chunk_call(m: Dict, offset: int, n: int) -> Dict[str, int]:
    """One chunked-prefill attention call: ``n`` queries at positions
    ``offset .. offset + n - 1`` over keys ``0 .. offset + n - 1``."""
    return {"flops": attn_flops(m, n, offset + n),
            "bytes": attn_bytes(m, n, offset + n)}


def request_work(m: Dict, prompt: int, n_out: int, chunk: int
                 ) -> Dict[str, int]:
    """Everything one served request needs.

    The prompt prefills whole when it is at most ``chunk`` tokens (flash
    attention, not paged) and otherwise in ``chunk``-token pieces through
    the paged prefill kernel; its last position goes through the head.
    Each of the ``n_out - 1`` later tokens is one decode step: matmuls,
    the head, and paged attention over the cache.
    """
    L = m["n_layers"]
    out = {"model_flops": prompt * L * layer_matmul_flops(m) + head_flops(m)
           + attn_flops(m, prompt, prompt),
           "decode_flops": 0, "decode_bytes": 0,
           "chunk_flops": 0, "chunk_bytes": 0}
    if chunk and prompt > chunk:
        for p in range(0, prompt, chunk):
            c = chunk_call(m, p, min(chunk, prompt - p))
            out["chunk_flops"] += c["flops"]
            out["chunk_bytes"] += c["bytes"]
    for j in range(1, n_out):
        c = decode_call(m, prompt + j)
        out["decode_flops"] += c["flops"]
        out["decode_bytes"] += c["bytes"]
    out["model_flops"] += (n_out - 1) * (L * layer_matmul_flops(m)
                                         + head_flops(m))
    out["model_flops"] += out["decode_flops"]
    return out
