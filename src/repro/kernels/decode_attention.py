"""Pallas TPU flash-decode: one query token against a long KV cache.

Grid: (B, KV, n_kv_blocks), kv innermost with (m, l, acc) VMEM scratch.
The per-sequence valid length arrives via scalar prefetch so fully-invalid
cache blocks are skipped (ring caches pass kv_len < capacity until wrapped).

q is laid out (B, KV, G, hd): all G query heads sharing a kv head are one
MXU matmul of shape (G, hd) x (hd, bkv).

The *paged* variants (:func:`paged_decode_attention`,
:func:`paged_prefill_attention`) read the KV cache through per-sequence
block tables:

* The pools reach ``pallas_call`` exactly as the engine stores them, a
  stack over layers, (n_layers, num_pages, page, KV * hd): a page is its
  tokens' rows with every KV head's ``hd`` lanes side by side, which the
  TPU stores row-major at any head dim.  No slice, pad, transpose or copy
  of the pool: the k/v BlockSpec is (1, 1, page, n * hd), one head
  group's lanes of one page, and its index map dereferences the layer and
  the block table, ``(layer, bt[b, j], 0, g)``, to DMA exactly the page a
  grid step needs: the gather never materializes in HBM.
* Heads are split in lanes, not rows.  A group of ``n`` KV heads is one
  grid step; head i's query rows carry its query in lanes [i * hd, (i +
  1) * hd) and exact zeros elsewhere, so one matmul against the group's
  page lanes gives each row its own head's scores (the other heads' terms
  are zero products), and each row keeps its own head's lanes of the
  output.  A head's rows fit a sublane tile (decode, at most 8): every KV
  head is one group, grid (B, 1, P), and the block is the whole page.
  More (a chunk): a group is the fewest heads that fill whole 128-lane
  tiles (one at head dim 128, two at 64), grid (B, KV / n, P), so a step
  reads only its heads' lanes of the page and VMEM holds only their rows.
  Pages past ``kv_len`` are skipped.
* Scalar-prefetch operands: ``kv_len`` (int32) FIRST, then the block
  table, the query offset, and ``layer`` LAST.  Keep ``kv_len`` first:
  the benchmark's trace reader names the paged kernels by a custom call
  whose first operand is ``s32[`` (``chipbench/devtrace.py``).
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
def _paged_kernel(kv_len_ref, bt_ref, qoff_ref, layer_ref, q_ref, k_ref,
                  v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                  page: int, heads_per_row: int, rows_per_head: int):
    """Shared paged attention body.

    One grid step = one (sequence, head group, logical page): k_ref and
    v_ref hold the group's lanes of the page, (1, 1, page, n * hd).  The
    q block is (1, 1, n * rows_per_head, n * hd): head i's rows carry
    its query in lanes [i * hd, (i + 1) * hd) and zeros elsewhere, so one
    matmul against the page gives every row its own head's scores
    exactly, and row r's own head's lanes of the output are its result.
    Row r sits at query position
    ``qoff + (r % rows_per_head) // heads_per_row``.
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
        q = q_ref[0, 0].astype(jnp.float32) * scale     # (rows, n * hd)
        k = k_ref[0, 0].astype(jnp.float32)              # (page, n * hd)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kp = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = qoff_ref[b] + (row % rows_per_head) // heads_per_row
        s = jnp.where((kp <= qpos) & (kp < valid_len), s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1)
        acc_scr[...] = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(j == n_pages - 1)
    def _out():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def _lane_group(KV: int, hd: int) -> int:
    """Fewest KV heads whose lanes fill whole 128-lane tiles (every head
    where no fewer do), so a group's lanes of a page are one block."""
    return next(n for n in range(1, KV + 1)
                if KV % n == 0 and (n * hd % 128 == 0 or n == KV))


def _paged_attention(q_rows: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                     block_tables: jax.Array, kv_len: jax.Array,
                     q_offset: jax.Array, layer, *, scale: float,
                     heads_per_row: int, interpret: bool) -> jax.Array:
    """q_rows: (B, KV, rows, hd) query rows per KV head; pools
    (n_layers, num_pages, page, KV * hd), passed to the kernel as they
    are and read at ``layer``; block_tables (B, P).  Returns the same
    rows layout (B, KV, rows, hd)."""
    B, KV, rows, hd = q_rows.shape
    page = k_pool.shape[2]
    P = block_tables.shape[1]
    # a head's rows fit one sublane tile (decode): every head in one step
    # and one matmul; else the fewest heads that fill whole lane tiles
    n = KV if rows <= 8 else _lane_group(KV, hd)
    groups = KV // n
    # head i of a group: its query in its own hd lanes, exact zeros
    # elsewhere (no arithmetic, so the scores are the per-head ones)
    eye = jnp.eye(n, dtype=bool)[:, None, :, None]           # (n,1,n,1)
    q_in = jnp.where(eye, q_rows.reshape(B, groups, n, rows, 1, hd), 0)
    q_in = q_in.astype(q_rows.dtype).reshape(B, groups, n * rows, n * hd)

    kernel = functools.partial(_paged_kernel, scale=scale, page=page,
                               heads_per_row=heads_per_row,
                               rows_per_head=rows)
    q_spec = pl.BlockSpec((1, 1, n * rows, n * hd),
                          lambda b, g, j, *_: (b, g, 0, 0))
    # the paged gather: layer and physical page id from scalar prefetch
    kv_spec = pl.BlockSpec((1, 1, page, n * hd),
                           lambda b, g, j, kv_len, bt, qoff, layer:
                           (layer[0], bt[b, j], 0, g))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,      # kv_len, block_tables, q_offset, layer
            grid=(B, groups, P),
            in_specs=[q_spec, kv_spec, kv_spec],
            out_specs=q_spec,
            scratch_shapes=[
                pltpu.VMEM((n * rows,), jnp.float32),
                pltpu.VMEM((n * rows,), jnp.float32),
                pltpu.VMEM((n * rows, n * hd), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q_in.shape, q_rows.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), block_tables.astype(jnp.int32),
      q_offset.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      q_in, k_pool, v_pool)
    # row r of head i keeps lanes [i * hd, (i + 1) * hd)
    out = jnp.diagonal(out.reshape(B, groups, n, rows, n, hd),
                       axis1=2, axis2=4)                 # (B,g,rows,hd,n)
    return jnp.moveaxis(out, -1, 2).reshape(B, KV, rows, hd)


def paged_decode_attention(q: jax.Array, k_pool: jax.Array,
                           v_pool: jax.Array, block_tables: jax.Array,
                           kv_len: jax.Array, layer, *,
                           softmax_scale: Optional[float] = None,
                           interpret: bool = False) -> jax.Array:
    """One query token per sequence against layer ``layer`` of a paged KV
    pool.

    q: (B, 1, H, hd); pools: (n_layers, num_pages, page, KV * hd);
    block_tables: (B, P) physical page ids (0 = reserved scratch page);
    kv_len: (B,).
    """
    B, one, H, hd = q.shape
    assert one == 1
    KV = k_pool.shape[3] // hd
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    q_rows = q.reshape(B, KV, G, hd)
    out = _paged_attention(q_rows, k_pool, v_pool, block_tables, kv_len,
                           jnp.maximum(kv_len - 1, 0), layer, scale=scale,
                           heads_per_row=G, interpret=interpret)
    return out.reshape(B, 1, H, hd)


def paged_prefill_attention(q: jax.Array, k_pool: jax.Array,
                            v_pool: jax.Array, block_tables: jax.Array,
                            kv_len: jax.Array, q_offset: jax.Array, layer, *,
                            softmax_scale: Optional[float] = None,
                            interpret: bool = False) -> jax.Array:
    """Chunked-prefill attention against layer ``layer`` of a paged pool.

    q: (B, C, H, hd) — the chunk's queries, at positions
    ``q_offset + [0, C)``; the chunk's own K/V must already be scattered
    into the pool, so ``kv_len = q_offset + C``.  Query rows flatten to
    (position, head-group) pairs so the whole chunk is one MXU operand.
    """
    B, C, H, hd = q.shape
    KV = k_pool.shape[3] // hd
    G = H // KV
    scale = softmax_scale if softmax_scale is not None else hd ** -0.5
    q_rows = q.reshape(B, C, KV, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, KV, C * G, hd)
    out = _paged_attention(q_rows, k_pool, v_pool, block_tables, kv_len,
                           q_offset, layer, scale=scale, heads_per_row=G,
                           interpret=interpret)
    return out.reshape(B, KV, C, G, hd).transpose(0, 2, 1, 3, 4) \
        .reshape(B, C, H, hd)
