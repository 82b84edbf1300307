"""Pallas TPU flash-decode: one query token against a long KV cache.

Grid: (B, KV, n_kv_blocks), kv innermost with (m, l, acc) VMEM scratch.
The per-sequence valid length arrives via scalar prefetch so fully-invalid
cache blocks are skipped (ring caches pass kv_len < capacity until wrapped).

q is laid out (B, KV, G, hd): all G query heads sharing a kv head are one
MXU matmul of shape (G, hd) x (hd, bkv).

The *paged* variants (:func:`paged_decode_attention`,
:func:`paged_prefill_attention`) read the KV cache through per-sequence
block tables: the pool is (num_pages, page, KV, hd) and the block table
(B, P) is the SECOND scalar-prefetch operand, so the k/v BlockSpec index
maps dereference ``bt_ref[b, j]`` to DMA exactly the physical page each
grid step needs — the gather never materializes in HBM.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
DEFAULT_BLOCK_KV = 1024


def _decode_kernel(kv_len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                   m_scr, l_scr, acc_scr, *, scale: float, block_kv: int):
    b = pl.program_id(0)
    h = pl.program_id(1)
    j = pl.program_id(2)
    n_kv = pl.num_programs(2)
    valid_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * block_kv < valid_len)
    def _compute():
        # int8-quantized caches dequantize in VMEM with per-(batch, kv-head)
        # scales (§Perf C: halves the HBM stream that dominates decode)
        q = q_ref[0, 0].astype(jnp.float32) * scale        # (G, hd)
        k = k_ref[0, 0].astype(jnp.float32) * ks_ref[b, h]  # (bkv, hd)
        v = v_ref[0, 0].astype(jnp.float32) * vs_ref[b, h]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # (G,bkv)
        kp = j * block_kv + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kp < valid_len, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_kv - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     kv_len: jax.Array, *,
                     softmax_scale: Optional[float] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_kv: int = DEFAULT_BLOCK_KV,
                     interpret: bool = False) -> jax.Array:
    """k_scale / v_scale: (B, KV) f32 dequantization scales for int8 caches
    (None = 1.0; required when k/v dtype is integer)."""
    B, one, H, hd = q.shape
    assert one == 1
    _, S, KV, _ = k.shape
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    if k_scale is None:
        k_scale = jnp.ones((B, KV), jnp.float32)
    if v_scale is None:
        v_scale = jnp.ones((B, KV), jnp.float32)

    hd_p = max(128, -(-hd // 128) * 128)
    g_p = max(8, -(-G // 8) * 8)                           # sublane alignment
    bkv = min(block_kv, max(128, -(-S // 128) * 128))
    s_p = -(-S // bkv) * bkv

    qt = q.reshape(B, KV, G, hd).transpose(0, 1, 2, 3)
    qt = jnp.pad(qt, ((0, 0), (0, 0), (0, g_p - G), (0, hd_p - hd)))
    kt = jnp.pad(k, ((0, 0), (0, s_p - S), (0, 0), (0, hd_p - hd))
                 ).transpose(0, 2, 1, 3)                   # (B,KV,s_p,hd_p)
    vt = jnp.pad(v, ((0, 0), (0, s_p - S), (0, 0), (0, hd_p - hd))
                 ).transpose(0, 2, 1, 3)

    grid = (B, KV, s_p // bkv)
    kernel = functools.partial(_decode_kernel, scale=scale, block_kv=bkv)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, g_p, hd_p), lambda b, h, j, *_: (b, h, 0, 0)),
                pl.BlockSpec((1, 1, bkv, hd_p), lambda b, h, j, *_: (b, h, j, 0)),
                pl.BlockSpec((1, 1, bkv, hd_p), lambda b, h, j, *_: (b, h, j, 0)),
                # scales: whole (B, KV) arrays in SMEM, read as scalars
                # (a (1, 1) VMEM block is not tile-aligned for Mosaic)
                pl.BlockSpec(memory_space=pltpu.SMEM),
                pl.BlockSpec(memory_space=pltpu.SMEM),
            ],
            out_specs=pl.BlockSpec((1, 1, g_p, hd_p),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g_p,), jnp.float32),
                pltpu.VMEM((g_p,), jnp.float32),
                pltpu.VMEM((g_p, hd_p), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, g_p, hd_p),
                                       q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qt, kt, vt,
      k_scale.astype(jnp.float32), v_scale.astype(jnp.float32))
    return out[:, :, :G, :hd].reshape(B, 1, H, hd)


# ======================================================================
# Paged variants: KV gathered through block tables via scalar prefetch
# ======================================================================
def _paged_kernel(kv_len_ref, bt_ref, qoff_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, page: int,
                  heads_per_row: int):
    """Shared paged attention body.

    One grid step = one (sequence, kv head, logical page).  Rows of the q
    block are flattened (chunk position, query-head group) pairs:
    row r is query position ``qoff + r // heads_per_row`` (decode is the
    C == 1 special case, where every row is the same single position).
    """
    b = pl.program_id(0)
    j = pl.program_id(2)
    n_pages = pl.num_programs(2)
    valid_len = kv_len_ref[b]

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(j * page < valid_len)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale         # (rows, hd)
        k = k_ref[0, 0].astype(jnp.float32)                 # (page_p, hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        kp = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = qoff_ref[b] + \
            jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads_per_row
        s = jnp.where((kp < valid_len) & (kp <= qpos), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _paged_attention(q_rows: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, kv_len: jax.Array,
                     q_offset: jax.Array, *, scale: float,
                     heads_per_row: int, interpret: bool) -> jax.Array:
    """q_rows: (B, KV, rows, hd) flattened query rows; pools
    (num_pages, page, KV, hd); block_tables (B, P). Returns the same
    rows layout (B, KV, rows, hd)."""
    B, KV, rows, hd = q_rows.shape
    num_pages, page, _, _ = k_pool.shape
    P = block_tables.shape[1]

    hd_p = max(128, -(-hd // 128) * 128)
    rows_p = max(8, -(-rows // 8) * 8)                     # sublane alignment
    page_p = max(8, -(-page // 8) * 8)

    qt = jnp.pad(q_rows, ((0, 0), (0, 0), (0, rows_p - rows),
                          (0, hd_p - hd)))
    # pool laid out (num_pages, KV, page_p, hd_p): one (page_p, hd_p) tile
    # per (physical page, kv head) — the unit the index map DMAs
    kt = jnp.pad(k_pool, ((0, 0), (0, page_p - page), (0, 0),
                          (0, hd_p - hd))).transpose(0, 2, 1, 3)
    vt = jnp.pad(v_pool, ((0, 0), (0, page_p - page), (0, 0),
                          (0, hd_p - hd))).transpose(0, 2, 1, 3)

    grid = (B, KV, P)
    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               heads_per_row=heads_per_row)

    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,      # kv_len, block_tables, q_offset
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, rows_p, hd_p),
                             lambda b, h, j, *_: (b, h, 0, 0)),
                # the paged gather: physical page id from the block table
                pl.BlockSpec((1, 1, page_p, hd_p),
                             lambda b, h, j, kv_len, bt, qoff:
                             (bt[b, j], h, 0, 0)),
                pl.BlockSpec((1, 1, page_p, hd_p),
                             lambda b, h, j, kv_len, bt, qoff:
                             (bt[b, j], h, 0, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, rows_p, hd_p),
                                   lambda b, h, j, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((rows_p,), jnp.float32),
                pltpu.VMEM((rows_p,), jnp.float32),
                pltpu.VMEM((rows_p, hd_p), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KV, rows_p, hd_p), q_rows.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
      q_offset.astype(jnp.int32), qt, kt, vt)
    return out[:, :, :rows, :hd]


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           kv_len: jax.Array, *,
                           softmax_scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """One query token per sequence against a paged KV pool.

    q: (B, 1, H, hd); pools: (num_pages, page, KV, hd); block_tables:
    (B, P) physical page ids (0 = reserved scratch page); kv_len: (B,).
    """
    B, one, H, hd = q.shape
    assert one == 1
    KV = k_pool.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    q_rows = q.reshape(B, KV, G, hd)
    out = _paged_attention(q_rows, k_pool, v_pool, block_tables, kv_len,
                           jnp.maximum(kv_len - 1, 0), scale=scale,
                           heads_per_row=G, interpret=interpret)
    return out.reshape(B, 1, H, hd)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            kv_len: jax.Array, q_offset: jax.Array, *,
                            softmax_scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """Chunked-prefill attention against a paged pool.

    q: (B, C, H, hd) — the chunk's queries, at positions
    ``q_offset + [0, C)``; the chunk's own K/V must already be scattered
    into the pool, so ``kv_len = q_offset + C``.  Query rows flatten to
    (position, head-group) pairs so the whole chunk is one MXU operand.
    """
    B, C, H, hd = q.shape
    KV = k_pool.shape[2]
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    q_rows = q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, C * G, hd)
    out = _paged_attention(q_rows, k_pool, v_pool, block_tables, kv_len,
                           q_offset, scale=scale, heads_per_row=G,
                           interpret=interpret)
    return out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)
