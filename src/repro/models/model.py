"""Model assembly: pattern-tiled layer stacks under ``lax.scan``.

The layer stack is ``cfg.pattern`` repeated; parameters for each pattern
position are stacked over periods so compile time is O(pattern), not
O(n_layers).  Remainder layers (e.g. recurrentgemma's 26 = 8x3 + 2) are
unrolled with their own parameters.

Public API:
  param_specs(cfg)                 -> Spec tree
  cache_specs(cfg, B, seq_len)     -> Spec tree (decode caches)
  forward(cfg, params, batch, ...) -> logits [, cache] [, aux]
  decode_step(cfg, params, cache, tokens, pos) -> logits, cache
  loss_fn(cfg, params, batch)      -> scalar
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import BlockKind, Family, ModelConfig
from repro.models import blocks as B
from repro.models.layers import cross_entropy, embed, embed_specs, rms_norm, unembed
from repro.models.param import Spec, init_params, tree_map_specs
from repro.models.sharding import constrain

AUX_LOSS_WEIGHT = 0.01


# ----------------------------------------------------------------------
# Spec assembly
# ----------------------------------------------------------------------
def _stack(specs, n: int):
    return tree_map_specs(
        lambda s: Spec((n,) + s.shape, ("layers",) + s.axes,
                       init=s.init, scale=s.scale, dtype=s.dtype), specs)


def _block_specs(cfg: ModelConfig, kind: BlockKind, cross: bool = False):
    if kind in (BlockKind.ATTN, BlockKind.LOCAL_ATTN, BlockKind.CHUNKED_ATTN):
        return B.attn_specs(cfg, kind, layer_idx=0, cross=cross)
    if kind == BlockKind.RGLRU:
        return B.rglru_specs(cfg)
    if kind == BlockKind.MLSTM:
        return B.mlstm_specs(cfg)
    if kind == BlockKind.SLSTM:
        return B.slstm_specs(cfg)
    raise ValueError(kind)


def _block_cache_specs(cfg: ModelConfig, kind: BlockKind, batch: int,
                       seq_len: int, cross: bool = False):
    if kind in (BlockKind.ATTN, BlockKind.LOCAL_ATTN, BlockKind.CHUNKED_ATTN):
        return B.attn_cache_specs(cfg, kind, batch, seq_len, cross=cross)
    if kind == BlockKind.RGLRU:
        return B.rglru_cache_specs(cfg, batch)
    if kind == BlockKind.MLSTM:
        return B.mlstm_cache_specs(cfg, batch)
    if kind == BlockKind.SLSTM:
        return B.slstm_cache_specs(cfg, batch)
    raise ValueError(kind)


def _layout(cfg: ModelConfig) -> Tuple[int, int]:
    """(n_periods, n_remainder)."""
    P = len(cfg.pattern)
    return cfg.n_layers // P, cfg.n_layers % P


def param_specs(cfg: ModelConfig) -> Dict[str, Any]:
    assert cfg.moe_every in (0, 1), "scan requires uniform MoE placement"
    n_periods, rem = _layout(cfg)
    cross = cfg.is_encdec
    specs: Dict[str, Any] = {
        "embed": embed_specs(cfg.padded_vocab, cfg.d_model,
                             cfg.tie_embeddings),
        "final_ln": Spec((cfg.d_model,), (None,), init="zeros"),
    }
    if n_periods:
        specs["blocks"] = {
            f"p{i}": _stack(_block_specs(cfg, kind, cross), n_periods)
            for i, kind in enumerate(cfg.pattern)}
    if rem:
        specs["rem"] = {
            f"r{j}": _block_specs(cfg, cfg.pattern[j % len(cfg.pattern)], cross)
            for j in range(rem)}
    if cfg.is_encdec:
        specs["encoder"] = {
            "blocks": _stack(B.attn_specs(cfg, BlockKind.ATTN),
                             cfg.n_encoder_layers),
            "final_ln": Spec((cfg.d_model,), (None,), init="zeros"),
        }
    return specs


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                kv_dtype: Optional[str] = None) -> Dict[str, Any]:
    """``kv_dtype``: override attention K/V cache dtype (§Perf: int8
    quantized cache halves decode HBM traffic; dequant scale handling lives
    in the TPU kernel, the model path upcasts)."""
    n_periods, rem = _layout(cfg)
    cross = cfg.is_encdec

    def bcs(kind):
        s = _block_cache_specs(cfg, kind, batch, seq_len, cross)
        if kv_dtype:
            s = {k: (dataclasses.replace(v, dtype=kv_dtype)
                     if k in ("k", "v") else v) for k, v in s.items()}
        return s

    specs: Dict[str, Any] = {}
    if n_periods:
        specs["blocks"] = {
            f"p{i}": _stack(bcs(kind), n_periods)
            for i, kind in enumerate(cfg.pattern)}
    if rem:
        specs["rem"] = {
            f"r{j}": bcs(cfg.pattern[j % len(cfg.pattern)])
            for j in range(rem)}
    return specs


def init_model_params(cfg: ModelConfig, key: jax.Array):
    return init_params(param_specs(cfg), key, cfg.dtype)


def init_cache(cfg: ModelConfig, batch: int, seq_len: int):
    zero_key = jax.random.PRNGKey(0)  # all-zeros init; key unused
    return init_params(cache_specs(cfg, batch, seq_len), zero_key, cfg.dtype)


# ----------------------------------------------------------------------
# Paged KV cache (serving): global-attention K/V live in a shared pool of
# (num_pages, page_size) token pages indexed through per-sequence block
# tables; every other cache leaf (ring caches for local/chunked attention,
# recurrent state, cross-attention K/V) stays per-slot — those are already
# O(1) or O(window) per sequence, so paging buys them nothing.
# ----------------------------------------------------------------------
def paged_cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                      num_pages: int, page_size: int,
                      kv_dtype: Optional[str] = None) -> Dict[str, Any]:
    """Like :func:`cache_specs`, with global-attention k/v replaced by
    pooled page arrays.  A layer's pool is (num_pages, page_size, KV * hd):
    a page is its tokens' rows of every KV head side by side, which the
    TPU stores row-major at any head dim (no lane padding, no re-layout).
    Layers stacked in one period share the pool SHAPE but each owns its
    pages (leading ``n_periods`` axis), addressed by the same block
    table; a remainder layer's pool is a one-layer stack, so every pool
    is read as (layer, page) of a stack."""
    n_periods, rem = _layout(cfg)
    cross = cfg.is_encdec
    KV, hd = cfg.n_kv_heads, cfg.hd

    def bcs(kind, stacked: bool):
        s = dict(_block_cache_specs(cfg, kind, batch, seq_len, cross))
        if kind == BlockKind.ATTN:
            pool = Spec((num_pages, page_size, KV * hd), (None, None, "kv"),
                        init="zeros")
            pools = {"k": pool, "v": pool}
            s.update(pools if stacked else _stack(pools, 1))
        if kv_dtype:
            s = {k: (dataclasses.replace(v, dtype=kv_dtype)
                     if k in ("k", "v") else v) for k, v in s.items()}
        return s

    specs: Dict[str, Any] = {}
    if n_periods:
        specs["blocks"] = {
            f"p{i}": _stack(bcs(kind, True), n_periods)
            for i, kind in enumerate(cfg.pattern)}
    if rem:
        specs["rem"] = {
            f"r{j}": bcs(cfg.pattern[j % len(cfg.pattern)], False)
            for j in range(rem)}
    return specs


def init_paged_cache(cfg: ModelConfig, batch: int, seq_len: int,
                     num_pages: int, page_size: int):
    zero_key = jax.random.PRNGKey(0)  # all-zeros init; key unused
    return init_params(paged_cache_specs(cfg, batch, seq_len,
                                         num_pages, page_size),
                       zero_key, cfg.dtype)


def paged_leaf_flags(cfg: ModelConfig, cache) -> list:
    """Per-leaf booleans (tree_flatten_with_path order): True for pooled
    global-attention k/v leaves, False for per-slot leaves.  The engine
    zips these against flattened caches to scatter/slice correctly."""
    flat, _ = jax.tree_util.tree_flatten_with_path(cache)

    def is_paged(path) -> bool:
        keys = [getattr(p, "key", None) for p in path]
        if keys[-1] not in ("k", "v"):
            return False
        if "blocks" in keys:
            kind = cfg.pattern[int(keys[keys.index("blocks") + 1][1:])]
        elif "rem" in keys:
            j = int(keys[keys.index("rem") + 1][1:])
            kind = cfg.pattern[j % len(cfg.pattern)]
        else:
            return False
        return kind == BlockKind.ATTN

    return [is_paged(path) for path, _ in flat]


def chunked_prefill_supported(cfg: ModelConfig) -> bool:
    """Chunked prefill covers every block kind with O(1) carried state:
    global attention (paged pool + explicit-position attention) and the
    recurrent kinds (state continuation).  Ring caches (local/chunked
    attention) and encoder-decoder cross-attention prefill whole."""
    ok = {BlockKind.ATTN, BlockKind.RGLRU, BlockKind.MLSTM, BlockKind.SLSTM}
    return not cfg.is_encdec and all(k in ok for k in cfg.pattern)


# ----------------------------------------------------------------------
# Block dispatch
# ----------------------------------------------------------------------
def _apply_block(cfg, kind: BlockKind, params, x, *, mode, cache, pos,
                 cross_x, cache_len, impl, block_tables=None, layer=0):
    if kind in (BlockKind.ATTN, BlockKind.LOCAL_ATTN, BlockKind.CHUNKED_ATTN):
        return B.attn_block(cfg, kind, params, x, mode=mode, cache=cache,
                            pos=pos, cross_x=cross_x, cache_len=cache_len,
                            impl=impl, block_tables=block_tables,
                            layer=layer)
    if kind == BlockKind.RGLRU:
        return B.rglru_block(cfg, params, x, mode=mode, cache=cache, impl=impl)
    if kind == BlockKind.MLSTM:
        return B.mlstm_block(cfg, params, x, mode=mode, cache=cache, impl=impl)
    if kind == BlockKind.SLSTM:
        return B.slstm_block(cfg, params, x, mode=mode, cache=cache, impl=impl)
    raise ValueError(kind)


# ----------------------------------------------------------------------
# Encoder (whisper)
# ----------------------------------------------------------------------
def _encode(cfg: ModelConfig, params, frames: jax.Array, impl,
            unroll: bool = False) -> jax.Array:
    """Bidirectional encoder over stub frame embeddings."""
    enc = params["encoder"]

    def body(x, p):
        x, _, _ = B.attn_block(cfg, BlockKind.ATTN, p, x, mode="train",
                               causal=False, impl=impl)
        return x, None

    if unroll:
        x = frames
        for i in range(cfg.n_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[i], enc["blocks"]))
    else:
        x, _ = jax.lax.scan(body, frames, enc["blocks"])
    return rms_norm(x, enc["final_ln"])


# ----------------------------------------------------------------------
# Forward
# ----------------------------------------------------------------------
def forward(cfg: ModelConfig, params, batch: Dict[str, jax.Array], *,
            mode: str = "train", cache=None, pos: Optional[jax.Array] = None,
            cache_len: Optional[int] = None, impl: Optional[str] = None,
            remat: bool = False, unroll: bool = False,
            remat_policy: Optional[str] = None,
            block_tables: Optional[jax.Array] = None):
    """Returns (logits, new_cache_or_None, aux_loss).

    ``batch``: tokens (B,S) [+ labels, + frames (audio), + patches (vlm)];
    decode mode: tokens (B,1) + pos (B,).
    ``chunk`` mode: one prefill chunk of an in-flight prompt — tokens
    (B,C) at positions ``pos + [0,C)`` (``pos`` scalar), consuming AND
    returning a full decode cache (recurrent state continues, attention
    K/V scatter into the paged pool).
    ``block_tables``: (B, P) physical page ids for paged global-attention
    caches (decode/chunk modes); None = dense per-slot caches.
    ``unroll``: Python loop over layer periods instead of lax.scan (used by
    the dry-run cost probes, where while-loop bodies are counted once).
    """
    # weight-only quantization (§Perf serving variant): integer weights are
    # stored narrow in HBM and upcast at use (XLA fuses the dequant into
    # the consumer on TPU; per-channel scales live in the serving kernel)
    if any(jnp.issubdtype(l.dtype, jnp.integer)
           for l in jax.tree.leaves(params)):
        wdt = jnp.dtype(cfg.dtype)
        params = jax.tree.map(
            lambda l: (l.astype(wdt) * jnp.asarray(0.01, wdt)
                       if jnp.issubdtype(l.dtype, jnp.integer) else l),
            params)

    tokens = batch["tokens"]
    x = embed(params["embed"], tokens, cfg.d_model)
    x = constrain(x, "batch", "seq", "embed")
    n_patches = 0
    if cfg.family == Family.VLM and mode != "decode" and "patches" in batch:
        patches = batch["patches"].astype(x.dtype)
        x = jnp.concatenate([patches, x], axis=1)
        n_patches = patches.shape[1]
    cross_x = None
    if cfg.is_encdec and mode != "decode":
        if mode == "chunk":
            raise NotImplementedError(
                "chunked prefill: encoder-decoder prefills whole "
                "(see chunked_prefill_supported)")
        # decode reads cross K/V from the cache; no encoder recompute
        cross_x = _encode(cfg, params, batch["frames"].astype(x.dtype), impl,
                          unroll=unroll)

    n_periods, rem = _layout(cfg)
    aux0 = jnp.float32(0.0)
    # paged global-attention pools ride the layer loop's carry whole: each
    # layer writes its tokens' rows in place and its attention reads its
    # own pages by index, so no layer's pool is sliced out or written back
    paged = block_tables is not None and mode in ("decode", "chunk")
    pooled = [f"p{i}" for i, kind in enumerate(cfg.pattern)
              if paged and kind == BlockKind.ATTN]

    def period_body(carry, xs):
        x, aux, pools, layer = carry
        x = constrain(x, "batch", "seq", "embed")
        p_params, p_cache = xs
        new_caches, new_pools = {}, {}
        for i, kind in enumerate(cfg.pattern):
            name = f"p{i}"
            c = p_cache[name] if p_cache is not None else None
            if name in pools:
                c = {**c, **pools[name]}
            x, nc, a = _apply_block(cfg, kind, p_params[name], x,
                                    mode=mode, cache=c, pos=pos,
                                    cross_x=cross_x, cache_len=cache_len,
                                    impl=impl, block_tables=block_tables,
                                    layer=layer)
            if nc is not None:
                if name in pools:
                    new_pools[name] = {k: nc.pop(k) for k in ("k", "v")}
                new_caches[name] = nc
            aux = aux + a
        x = constrain(x, "batch", "seq", "embed")
        return (x, aux, new_pools, layer + 1), (new_caches or None)

    body = period_body
    if remat:
        policy = None
        if remat_policy == "dots":
            # save matmul outputs, recompute elementwise/norms (§Perf):
            # fewer backward re-gathers of FSDP weights at moderate memory
            policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
        body = jax.checkpoint(period_body, prevent_cse=False, policy=policy)

    new_cache: Dict[str, Any] = {}
    if n_periods:
        p_cache = cache["blocks"] if cache is not None else None
        pools = {n: {k: p_cache[n][k] for k in ("k", "v")} for n in pooled}
        if pooled:
            p_cache = {n: {k: v for k, v in c.items()
                           if not (n in pools and k in ("k", "v"))}
                       for n, c in p_cache.items()}
        xs = (params["blocks"], p_cache)
        carry = (x, aux0, pools, jnp.int32(0))
        if unroll:
            ys_list = []
            for pi in range(n_periods):
                xs_i = jax.tree.map(lambda a: a[pi], xs)
                carry, y = body(carry, xs_i)
                ys_list.append(y)
            ys = (jax.tree.map(lambda *ls: jnp.stack(ls), *ys_list)
                  if ys_list and ys_list[0] is not None else None)
        else:
            carry, ys = jax.lax.scan(body, carry, xs)
        x, aux, pools, _ = carry
        if ys is not None and mode != "train":
            for n in pools:
                ys[n].update(pools[n])
            new_cache["blocks"] = ys
    else:
        aux = aux0
    for j in range(rem):
        kind = cfg.pattern[j % len(cfg.pattern)]
        c = cache["rem"][f"r{j}"] if cache is not None else None
        x, nc, a = _apply_block(cfg, kind, params["rem"][f"r{j}"], x,
                                mode=mode, cache=c, pos=pos, cross_x=cross_x,
                                cache_len=cache_len, impl=impl,
                                block_tables=block_tables)
        if nc is not None and mode != "train":
            new_cache.setdefault("rem", {})[f"r{j}"] = nc
        aux = aux + a

    x = rms_norm(x, params["final_ln"])
    if n_patches:
        x = x[:, n_patches:]
    if mode in ("prefill", "chunk"):
        # serving only needs the next-token distribution — unembed the last
        # position only (32k-position logits would dominate prefill cost)
        x = x[:, -1:]
    logits = unembed(params["embed"], x, cfg.tie_embeddings)
    logits = constrain(logits, "batch", "seq", "vocab")
    return logits, (new_cache or None), aux


def prefill(cfg: ModelConfig, params, batch, *, cache_len=None, impl=None,
            unroll=False):
    """Run the prompt; returns (last-position logits, populated cache)."""
    logits, cache, _ = forward(cfg, params, batch, mode="prefill",
                               cache_len=cache_len, impl=impl, unroll=unroll)
    return logits[:, -1:], cache


def decode_step(cfg: ModelConfig, params, cache, tokens: jax.Array,
                pos: jax.Array, *, impl=None, unroll=False,
                block_tables: Optional[jax.Array] = None):
    """One token per sequence against the cache. Returns (logits, cache).

    ``block_tables``: (B, P) page ids when the cache's global-attention
    K/V leaves are paged pools (serving engine); None for dense caches.
    """
    logits, new_cache, _ = forward(cfg, params, {"tokens": tokens},
                                   mode="decode", cache=cache, pos=pos,
                                   impl=impl, unroll=unroll,
                                   block_tables=block_tables)
    return logits, new_cache


def prefill_chunk(cfg: ModelConfig, params, cache, tokens: jax.Array,
                  pos: jax.Array, block_tables: Optional[jax.Array], *,
                  impl=None):
    """Advance an in-flight prompt by one chunk.

    tokens: (B, C) chunk at positions ``pos + [0, C)`` (``pos`` scalar,
    0 for the first chunk); ``cache`` carries recurrent state and the
    paged attention pool between chunks.  Returns (last-position logits,
    updated cache) — the logits only mean "next token" once the final
    chunk has run.
    """
    logits, new_cache, _ = forward(cfg, params, {"tokens": tokens},
                                   mode="chunk", cache=cache, pos=pos,
                                   impl=impl, block_tables=block_tables)
    return logits[:, -1:], new_cache


def loss_fn(cfg: ModelConfig, params, batch, *, impl=None, remat=False,
            unroll=False, remat_policy=None):
    logits, _, aux = forward(cfg, params, batch, mode="train", impl=impl,
                             remat=remat, unroll=unroll,
                             remat_policy=remat_policy)
    loss = cross_entropy(logits, batch["labels"])
    return loss + AUX_LOSS_WEIGHT * aux
