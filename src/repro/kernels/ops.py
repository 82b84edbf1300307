"""Jit-ready kernel entry points with implementation dispatch.

``impl``:
  * ``"xla"``      — efficient pure-jnp path (blocked flash attention,
                     ``jax.lax.ragged_dot`` for MoE, associative scan for the
                     LRU).  Default off-TPU; also the dry-run/roofline path.
  * ``"pallas"``   — Mosaic TPU kernels (the deployment path).
  * ``"interpret"``— Pallas kernels under ``interpret=True`` (CPU validation).
  * ``"ref"``      — the obviously-correct oracles in :mod:`repro.kernels.ref`.
  * ``None``       — auto: pallas on TPU backends, else xla.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref


def _auto_impl() -> str:
    return "pallas" if jax.default_backend() == "tpu" else "xla"


def _resolve(impl: Optional[str]) -> str:
    return impl or _auto_impl()


# ----------------------------------------------------------------------
# Attention
# ----------------------------------------------------------------------
def flash_attention(q, k, v, *, causal=True, window=0, chunk=0,
                    softmax_scale=None, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "xla_noattn":
        # dry-run cost probe: attention stubbed to a cheap shape-correct op;
        # its FLOPs/bytes are added analytically (roofline/analytic.py)
        B, Sq, H, hd = q.shape
        KV = k.shape[2]
        vm = jnp.mean(v, axis=1, keepdims=True)          # (B,1,KV,hd)
        out = jnp.broadcast_to(vm[:, :, :, None, :],
                               (B, Sq, KV, H // KV, hd))
        return out.reshape(B, Sq, H, hd).astype(q.dtype)
    if impl == "xla_full":   # dry-run cost probes: loop-free lowering
        return ref.full_attention(q, k, v, causal=causal, window=window,
                                  chunk=chunk, softmax_scale=softmax_scale)
    if impl in ("xla", "ref"):
        return ref.flash_attention(q, k, v, causal=causal, window=window,
                                   chunk=chunk, softmax_scale=softmax_scale)
    from repro.kernels import flash_attention as fa
    return fa.flash_attention(q, k, v, causal=causal, window=window,
                              chunk=chunk, softmax_scale=softmax_scale,
                              interpret=(impl == "interpret"))


def decode_attention(q, k, v, kv_len, *, softmax_scale=None,
                     impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl in ("xla", "ref", "xla_full", "xla_noattn"):
        return ref.decode_attention(q, k, v, kv_len, softmax_scale=softmax_scale)
    from repro.kernels import decode_attention as da
    return da.decode_attention(q, k, v, kv_len, softmax_scale=softmax_scale,
                               interpret=(impl == "interpret"))


def paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len, layer, *,
                           softmax_scale=None, impl: Optional[str] = None):
    """Single-step attention through per-sequence block tables against
    layer ``layer`` of the stacked pools (the paged serving engine's
    decode hot path)."""
    impl = _resolve(impl)
    if impl in ("xla", "ref", "xla_full", "xla_noattn"):
        return ref.paged_decode_attention(q, k_pool, v_pool, block_tables,
                                          kv_len, layer,
                                          softmax_scale=softmax_scale)
    from repro.kernels import decode_attention as da
    return da.paged_decode_attention(q, k_pool, v_pool, block_tables, kv_len,
                                     layer, softmax_scale=softmax_scale,
                                     interpret=(impl == "interpret"))


def paged_prefill_attention(q, k_pool, v_pool, block_tables, kv_len,
                            q_offset, layer, *, softmax_scale=None,
                            impl: Optional[str] = None):
    """Chunked-prefill attention through block tables against layer
    ``layer`` of the stacked pools (chunk K/V already scattered into the
    pool before the call)."""
    impl = _resolve(impl)
    if impl in ("xla", "ref", "xla_full", "xla_noattn"):
        return ref.paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                           kv_len, q_offset, layer,
                                           softmax_scale=softmax_scale)
    from repro.kernels import decode_attention as da
    return da.paged_prefill_attention(q, k_pool, v_pool, block_tables,
                                      kv_len, q_offset, layer,
                                      softmax_scale=softmax_scale,
                                      interpret=(impl == "interpret"))


# ----------------------------------------------------------------------
# MoE grouped matmul
# ----------------------------------------------------------------------
def moe_gmm(x, w, group_sizes, *, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "ref":
        return ref.moe_gmm(x, w, group_sizes)
    if impl in ("xla_noattn", "xla_full"):
        # cost-probe proxy: one dense (T,K)x(K,N) matmul has EXACTLY the
        # FLOPs of a perfect grouped matmul (groups sum to T), whereas the
        # CPU ragged_dot decomposition is dense-per-expert (E x FLOPs).
        # Expert-weight streaming bytes are added analytically.
        return jnp.einsum("tk,kn->tn", x, w[0],
                          preferred_element_type=x.dtype)
    if impl == "xla":
        return jax.lax.ragged_dot(x, w, group_sizes.astype(jnp.int32)
                                  ).astype(x.dtype)
    from repro.kernels import moe_gmm as gm
    return gm.moe_gmm(x, w, group_sizes, interpret=(impl == "interpret"))


# ----------------------------------------------------------------------
# RG-LRU scan
# ----------------------------------------------------------------------
def rglru_scan(a, b, h0=None, *, impl: Optional[str] = None):
    impl = _resolve(impl)
    if impl == "xla_noattn":
        # probe stub: the associative scan's log-depth passes over-count
        # HBM traffic vs the single-pass Pallas kernel; modeled analytically
        return b + a * 0.0
    if impl in ("xla", "ref", "xla_full"):
        return ref.rglru_scan(a, b, h0)
    from repro.kernels import rglru_scan as rs
    return rs.rglru_scan(a, b, h0, interpret=(impl == "interpret"))
