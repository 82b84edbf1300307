"""Plain float32 reference of the served dense decoder, its weights, and
the lower-precision control.

It imports nothing of the program under test.  The weights are made here,
from the run's seed, in the layout the serving engine is handed
(``make_weights``); the reference reads the same arrays back, upcast to
float32, layer by layer.

The architecture is the repository's dense decoder, which departs from
the published models in ways the configuration files list: the token
embedding is multiplied by sqrt(d_model), and every RMSNorm scales by
``1 + w``.  Per layer::

    h = rmsnorm(x) * (1 + ln1)
    q, k, v = h @ wq, h @ wk, h @ wv           (rotary: half-split, theta)
    x = x + causal_gqa_attention(q, k, v) @ wo
    h = rmsnorm(x) * (1 + ln2)
    x = x + (silu(h @ wg) * (h @ wu)) @ wd

then ``logits = (rmsnorm(x) * (1 + final_ln)) @ tok.T`` (tied) or
``@ head`` (untied).  RMSNorm's epsilon is 1e-6.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0
# the token embedding's rows have this norm.  The program multiplies the
# embedding by sqrt(d_model), and a tied head reads the same rows back, so
# a row's logit for its own token grows as sqrt(d_model) * norm**2: at a
# norm near 1 every position predicts its own input, greedy decoding
# repeats one token, and no lower precision changes a served token
EMBED_NORM = 0.1


def padded_vocab(vocab: int) -> int:
    """The served vocabulary: rounded up to a multiple of 256 above 1024
    (the dead rows of the embedding are zero, so no padded id can win)."""
    if vocab % 256 == 0 or vocab <= 1024:
        return vocab
    return -(-vocab // 256) * 256


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any whole number (seeds may exceed 32 bits)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def weight_shapes(m: Dict) -> Dict:
    """{path: (shape, kind)} of the served tree; ``m`` is the model block
    of a configuration file."""
    d, L, f = m["d_model"], m["n_layers"], m["d_ff"]
    H, KV, hd = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    V = padded_vocab(m["vocab"])
    s = {
        "embed/tok": ((V, d), "embed"),
        "final_ln": ((d,), "norm"),
        "blocks/p0/ln1": ((L, d), "norm"),
        "blocks/p0/wq": ((L, d, H * hd), "w"),
        "blocks/p0/wk": ((L, d, KV * hd), "w"),
        "blocks/p0/wv": ((L, d, KV * hd), "w"),
        "blocks/p0/wo": ((L, H * hd, d), "w"),
        "blocks/p0/ln2": ((L, d), "norm"),
        "blocks/p0/wg": ((L, d, f), "w"),
        "blocks/p0/wu": ((L, d, f), "w"),
        "blocks/p0/wd": ((L, f, d), "w"),
    }
    if not m["tie_embeddings"]:
        s["embed/head"] = ((d, V), "head")
    return s


def _nest(flat: Dict[str, jax.Array]) -> Dict:
    out: Dict = {}
    for path, leaf in flat.items():
        node = out
        *parents, name = path.split("/")
        for p in parents:
            node = node.setdefault(p, {})
        node[name] = leaf
    return out


@functools.partial(jax.jit, static_argnums=(0, 2))
def _make(spec: tuple, key: jax.Array, vocab: int):
    flat = {}
    for i, (path, shape, kind) in enumerate(spec):
        k = jax.random.fold_in(key, i)
        if kind == "norm":      # small, so a reference that drops it fails
            w = 0.1 * jax.random.normal(k, shape, jnp.float32)
        elif kind == "embed":
            w = (EMBED_NORM / np.sqrt(shape[1])
                 * jax.random.normal(k, shape, jnp.float32))
            w = jnp.where(jnp.arange(shape[0])[:, None] < vocab, w, 0.0)
        elif kind == "head":
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[0])
            w = jnp.where(jnp.arange(shape[1])[None, :] < vocab, w, 0.0)
        else:                   # 1/sqrt(fan_in), as the program's own init
            w = jax.random.normal(k, shape, jnp.float32) / np.sqrt(shape[-2])
        flat[path] = w.astype(jnp.bfloat16)
    return _nest(flat)


def make_weights(m: Dict, seed: int) -> Dict:
    """The served weights, bfloat16, made on the default device in one
    jitted call from ``seed``."""
    spec = tuple((p, shape, kind)
                 for p, (shape, kind) in sorted(weight_shapes(m).items()))
    return _make(spec, seed_key(seed), int(m["vocab"]))


# ----------------------------------------------------------------------
# the forward, float32 at "highest" precision (or fp8 for the control)
# ----------------------------------------------------------------------
def _q8(x: jax.Array, axis) -> jax.Array:
    """Round through fp8 (e4m3) with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    return (x / scale).astype(FP8).astype(jnp.float32) * scale


def _mm(a: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """a (..., K) @ w (K, N); the control rounds a per row and w per
    output column to fp8."""
    if fp8:
        a, w = _q8(a, -1), _q8(w, 0)
    return jnp.einsum("...k,kn->...n", a, w)


def _rms(x: jax.Array, w: jax.Array) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + EPS) * (1.0 + w)


def _rope(x: jax.Array, theta: float) -> jax.Array:
    """x (T, heads, hd) at positions 0..T-1, half-split rotation."""
    T, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None, None] * freq
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


Q_BLOCK = 512


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(dims: tuple, x: jax.Array, w: Dict, fp8: bool) -> jax.Array:
    """One decoder layer over one sequence x (T, d), float32."""
    H, KV, hd, theta = dims
    T = x.shape[0]
    G = H // KV
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    h = _rms(x, w["ln1"])
    q = _rope(_mm(h, w["wq"], fp8).reshape(T, H, hd), theta)
    k = _rope(_mm(h, w["wk"], fp8).reshape(T, KV, hd), theta)
    v = _mm(h, w["wv"], fp8).reshape(T, KV, hd)
    if fp8:
        q, k, v = _q8(q, -1), _q8(k, -1), _q8(v, -1)
    q = q.reshape(T, KV, G, hd) * hd ** -0.5
    outs = []
    for s0 in range(0, T, Q_BLOCK):          # query blocks bound memory
        qb = q[s0:s0 + Q_BLOCK]
        n = qb.shape[0]
        sc = jnp.einsum("qkgd,tkd->kgqt", qb, k)
        mask = (s0 + jnp.arange(n))[:, None] >= jnp.arange(T)[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        if fp8:
            p = _q8(p, -1)
        outs.append(jnp.einsum("kgqt,tkd->qkgd", p, v).reshape(n, H * hd))
    x = x + _mm(jnp.concatenate(outs, axis=0), w["wo"], fp8)
    h = _rms(x, w["ln2"])
    g = _mm(h, w["wg"], fp8)
    u = _mm(h, w["wu"], fp8)
    return x + _mm(jax.nn.silu(g) * u, w["wd"], fp8)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tok: jax.Array, ids: jax.Array, d: int) -> jax.Array:
    return tok[ids].astype(jnp.float32) * np.float32(np.sqrt(d))


@functools.partial(jax.jit, static_argnums=(4,))
def _head(x_rows: jax.Array, final_ln: jax.Array, tok: jax.Array,
          head, fp8: bool) -> jax.Array:
    h = _rms(x_rows, final_ln.astype(jnp.float32))
    w = (tok.T if head is None else head).astype(jnp.float32)
    return _mm(h, w, fp8)


def logits_at(m: Dict, weights: Dict, seqs: Sequence[Sequence[int]],
              starts: Sequence[int], n_rows: int, *,
              fp8: bool = False) -> np.ndarray:
    """Next-token logits of each sequence at rows ``start .. start +
    n_rows - 1`` (float32, shape (len(seqs), n_rows, padded vocab)).

    Every sequence is padded to one length (a multiple of 512) so each
    layer compiles once; under causal attention the padding never reaches
    an earlier row.  Runs layer by layer, one sequence at a time."""
    T = max(max(len(s) for s in seqs), max(starts) + n_rows)
    T = -(-T // Q_BLOCK) * Q_BLOCK
    dims = (m["n_heads"], m["n_kv_heads"], m["head_dim"],
            float(m["rope_theta"]))
    blocks = weights["blocks"]["p0"]
    out: List[np.ndarray] = []
    with jax.default_matmul_precision("highest"):
        for seq, start in zip(seqs, starts):
            ids = np.zeros((T,), np.int32)
            ids[:len(seq)] = seq
            x = _embed(weights["embed"]["tok"], jnp.asarray(ids),
                       m["d_model"])
            for i in range(m["n_layers"]):
                x = _layer(dims, x, {k: v[i] for k, v in blocks.items()},
                           fp8)
            rows = jax.lax.dynamic_slice_in_dim(x, start, n_rows, axis=0)
            out.append(np.asarray(_head(rows, weights["final_ln"],
                                        weights["embed"]["tok"],
                                        weights["embed"].get("head"), fp8)))
    return np.stack(out)


def served_gaps(m: Dict, weights: Dict, prompts: Sequence[Sequence[int]],
                outputs: Sequence[Sequence[int]], *,
                control: bool = False) -> np.ndarray:
    """Per served token, how far its reference logit lies below the
    reference's best at that position: 0 where the served token is the
    reference's own argmax.  Shape (n_requests, longest output); rows
    past a request's output are NaN.

    ``control``: in place of the served tokens, read the gap of the token
    that the same forward in fp8 ranks first at each position."""
    n = max(len(o) for o in outputs)
    seqs = [list(p) + list(o[:-1]) for p, o in zip(prompts, outputs)]
    starts = [len(p) - 1 for p in prompts]
    # rows past the end of a short sequence read padding and are dropped
    ref = logits_at(m, weights, seqs, starts, n)
    best = ref.max(axis=-1)
    if control:
        picks = logits_at(m, weights, seqs, starts, n,
                          fp8=True).argmax(axis=-1)
    else:
        picks = np.zeros(best.shape, np.int64)
        for i, o in enumerate(outputs):
            picks[i, :len(o)] = o
    got = np.take_along_axis(ref, picks[..., None], axis=-1)[..., 0]
    gaps = best - got
    for i, o in enumerate(outputs):
        gaps[i, len(o):] = np.nan
    return gaps
