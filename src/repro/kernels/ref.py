"""Pure-jnp reference oracles for every Pallas kernel.

These are the ground truth the kernels are tested against AND the execution
path used when the backend cannot run Mosaic (CPU dry-run / smoke tests).
They are written memory-bounded (blocked) so the full 32k/500k shapes lower
without materializing S×S score matrices.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _gqa_scores(q, k):
    """q: (B, bq, KV, G, hd), k: (B, bk, KV, hd) -> (B, KV, G, bq, bk)."""
    return jnp.einsum("bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = 0, chunk: int = 0,
                    softmax_scale: Optional[float] = None,
                    block_q: int = 512, block_kv: int = 1024) -> jax.Array:
    """Blocked exact attention with online softmax.

    q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd); H a multiple of KV (GQA).
    ``window``: sliding-window causal attention (each query sees the last
    ``window`` keys).  ``chunk``: chunked local attention (llama4 "iRoPE"
    style — attention does not cross ``chunk`` boundaries).
    Returns (B, Sq, H, hd) in q.dtype.
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    orig_sq = Sq

    bq = min(block_q, Sq)
    if Sq % bq:  # pad queries to a block multiple
        pad = bq - Sq % bq
        q = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Sq = q.shape[1]
    bkv = min(block_kv, Skv)
    if Skv % bkv:
        pad = bkv - Skv % bkv
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    Skv_p = k.shape[1]
    n_q, n_kv = Sq // bq, Skv_p // bkv

    q = (q.astype(jnp.float32) * scale).astype(q.dtype)
    qr = q.reshape(B, n_q, bq, KV, G, hd)
    kr = k.reshape(B, n_kv, bkv, KV, hd)
    vr = v.reshape(B, n_kv, bkv, KV, hd)

    # assume q positions are the LAST Sq positions of the kv sequence
    # (prefill: Sq == Skv; decode-with-history handled by decode_attention)
    q_pos0 = Skv - orig_sq

    def q_block(i, q_i):
        # online softmax over kv blocks
        def kv_step(carry, j):
            m, l, acc = carry
            k_j = kr[:, j]
            v_j = vr[:, j]
            s = _gqa_scores(q_i, k_j)  # (B, KV, G, bq, bkv) f32
            qpos = q_pos0 + i * bq + jnp.arange(bq)
            kpos = j * bkv + jnp.arange(bkv)
            mask = kpos[None, :] < Skv  # kv padding
            if causal:
                mask = mask & (kpos[None, :] <= qpos[:, None])
            if window:
                mask = mask & (kpos[None, :] > qpos[:, None] - window)
            if chunk:
                mask = mask & (kpos[None, :] // chunk == qpos[:, None] // chunk)
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(axis=-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1)
            acc_new = acc * alpha[..., None] + jnp.einsum(
                "bkgqs,bskd->bkgqd", p.astype(v_j.dtype), v_j,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KV, G, bq), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KV, G, bq), jnp.float32)
        a0 = jnp.zeros((B, KV, G, bq, hd), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), jnp.arange(n_kv))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        # (B, KV, G, bq, hd) -> (B, bq, KV*G, hd)
        return out.transpose(0, 3, 1, 2, 4).reshape(B, bq, H, hd)

    outs = jax.lax.map(lambda i: q_block(i, qr[:, i]), jnp.arange(n_q))
    out = outs.transpose(1, 0, 2, 3, 4).reshape(B, Sq, H, hd)
    return out[:, :orig_sq].astype(q.dtype)


def full_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int = 0, chunk: int = 0,
                   softmax_scale: Optional[float] = None) -> jax.Array:
    """Unblocked masked attention — one einsum pair, no loops.

    Used by the dry-run cost probes: ``cost_analysis`` counts while-loop
    bodies once, so the blocked implementation under-reports FLOPs; this
    path makes every attention FLOP visible to the analyzer.  (It would be
    memory-infeasible to *execute* at 32k — probes are lowered, never run.)
    """
    B, Sq, H, hd = q.shape
    _, Skv, KV, _ = k.shape
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qr = q.reshape(B, Sq, KV, G, hd)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr.astype(jnp.float32) * scale,
                   k.astype(jnp.float32))
    qpos = (Skv - Sq) + jnp.arange(Sq)
    kpos = jnp.arange(Skv)
    mask = jnp.ones((Sq, Skv), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window:
        mask &= kpos[None, :] > qpos[:, None] - window
    if chunk:
        mask &= (kpos[None, :] // chunk) == (qpos[:, None] // chunk)
    s = jnp.where(mask[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return out.reshape(B, Sq, H, hd).astype(q.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *,
                     softmax_scale: Optional[float] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None) -> jax.Array:
    """Single-step GQA attention over a KV cache.

    q: (B, 1, H, hd); k, v: (B, S_cache, KV, hd); kv_len: (B,) number of
    valid cache slots (slot order is irrelevant to softmax, so ring-buffer
    caches pass a full-validity length once wrapped).
    k_scale / v_scale: (B, KV) dequantization scales for int8 caches.
    """
    B, _, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale[:, None, :, None].astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale[:, None, :, None].astype(jnp.float32)
    k, v = kf, vf
    qr = q.reshape(B, KV, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bkgd,bskd->bkgs", qr, k.astype(jnp.float32))
    valid = jnp.arange(S)[None] < kv_len[:, None]          # (B, S)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def gather_pages(pool: jax.Array, block_tables: jax.Array, layer,
                 hd: int) -> jax.Array:
    """Gather layer ``layer`` of a paged KV pool into per-sequence
    contiguous caches.

    pool: (n_layers, num_pages, page, KV * hd); block_tables: (B, P)
    physical page ids (0 = the reserved scratch page for unmapped logical
    pages).  Returns (B, P*page, KV, hd) — logical position p of sequence
    b lives at row p of its gather, so a plain ``kv_len`` mask recovers
    validity.
    """
    B, P = block_tables.shape
    page, width = pool.shape[2], pool.shape[3]
    return pool[layer, block_tables].reshape(B, P * page, width // hd, hd)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           kv_len: jax.Array, layer, *,
                           softmax_scale: Optional[float] = None
                           ) -> jax.Array:
    """Single-step GQA attention through block tables, against layer
    ``layer`` of (n_layers, num_pages, page, KV * hd) pools.

    Semantically ``decode_attention`` over the gathered pages: the mapped
    prefix [0, kv_len) of each sequence's gather is its KV history in
    order, everything past it (partial last page + scratch-page rows) is
    masked by ``kv_len``.
    """
    hd = q.shape[-1]
    return decode_attention(q, gather_pages(k_pool, block_tables, layer, hd),
                            gather_pages(v_pool, block_tables, layer, hd),
                            kv_len, softmax_scale=softmax_scale)


def chunk_prefill_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            kv_len: jax.Array, q_offset: jax.Array, *,
                            softmax_scale: Optional[float] = None
                            ) -> jax.Array:
    """Causal attention for a prefill *chunk* with explicit positions.

    Unlike :func:`flash_attention` (which assumes the queries are the last
    Sq positions of the kv array), the chunk's queries sit at positions
    ``q_offset + [0, C)`` inside a cache of ``kv_len`` valid positions —
    the chunk's own K/V were already scattered into the cache, so
    ``kv_len = q_offset + C`` and the causal mask handles intra-chunk
    ordering.  q: (B, C, H, hd); k, v: (B, S, KV, hd); kv_len, q_offset:
    (B,).  Normalization follows the flash path (p @ v then divide) so a
    one-chunk prefill reproduces full-prefill arithmetic.
    """
    B, C, H, hd = q.shape
    _, S, KV, _ = k.shape
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    qr = q.reshape(B, C, KV, G, hd).astype(jnp.float32) * scale
    s = jnp.einsum("bqkgd,bskd->bkgqs", qr, k.astype(jnp.float32))
    qpos = q_offset[:, None] + jnp.arange(C)[None, :]       # (B, C)
    kpos = jnp.arange(S)[None, :]                           # (1, S)
    mask = (kpos[:, None] <= qpos[..., None]) & \
        (kpos < kv_len[:, None])[:, None]                   # (B, C, S)
    s = jnp.where(mask[:, None, None], s, NEG_INF)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(p.sum(axis=-1), 1e-30)
    out = jnp.einsum("bkgqs,bskd->bkgqd", p, v.astype(jnp.float32))
    out = out / l[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(B, C, H, hd).astype(q.dtype)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            kv_len: jax.Array, q_offset: jax.Array, layer, *,
                            softmax_scale: Optional[float] = None
                            ) -> jax.Array:
    """Chunked-prefill attention through block tables against layer
    ``layer`` (chunk K/V already scattered into the pool pages before the
    call)."""
    hd = q.shape[-1]
    return chunk_prefill_attention(
        q, gather_pages(k_pool, block_tables, layer, hd),
        gather_pages(v_pool, block_tables, layer, hd),
        kv_len, q_offset, softmax_scale=softmax_scale)


def moe_gmm(x: jax.Array, w: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """Grouped (expert) matmul oracle: rows of ``x`` are sorted by expert.

    x: (T, K); w: (E, K, N); group_sizes: (E,) with sum == T.
    Reference semantics match ``jax.lax.ragged_dot`` — computed here the
    slow, obviously-correct way (mask per expert).
    """
    T, K = x.shape
    E, _, N = w.shape
    bounds = jnp.cumsum(group_sizes)
    starts = bounds - group_sizes
    rows = jnp.arange(T)
    out = jnp.zeros((T, N), jnp.promote_types(x.dtype, w.dtype))
    for e in range(E):
        mask = (rows >= starts[e]) & (rows < bounds[e])
        contrib = x @ w[e]
        out = out + jnp.where(mask[:, None], contrib, 0)
    return out.astype(x.dtype)


def rglru_scan(a: jax.Array, b: jax.Array,
               h0: Optional[jax.Array] = None) -> jax.Array:
    """Diagonal linear recurrence h_t = a_t * h_{t-1} + b_t (RG-LRU core).

    a, b: (B, S, D). Returns h: (B, S, D). Log-depth associative scan —
    the XLA path; the Pallas kernel does a time-blocked sequential scan.
    """
    if h0 is not None:
        # fold the initial state into the first step
        b = b.at[:, 0].add(a[:, 0] * h0)
        a = a.at[:, 0].set(jnp.zeros_like(a[:, 0]))

    def comb(x, y):
        a1, b1 = x
        a2, b2 = y
        return a2 * a1, a2 * b1 + b2

    _, h = jax.lax.associative_scan(comb, (a, b), axis=1)
    return h
