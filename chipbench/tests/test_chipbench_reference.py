"""The float32 reference against the program at a CPU size.

The program's prefill (whole, and in chunks through the paged pool) and
its paged decode step produce next-token logits that the reference must
match on the same weights; served tokens of the engine then lie at the
reference's best logit, and an altered one does not."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
from references import dense_decoder as R

M_SMALL = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=2,
               head_dim=64, d_ff=512, vocab=1500, rope_theta=10000.0,
               tie_embeddings=False)
PAGE, N = 16, 70


def program_cfg(m, dtype):
    from repro.configs import get_config
    return dataclasses.replace(
        get_config("deepseek-7b-smoke"), n_layers=m["n_layers"],
        d_model=m["d_model"], n_heads=m["n_heads"],
        n_kv_heads=m["n_kv_heads"], head_dim=m["head_dim"], d_ff=m["d_ff"],
        vocab=m["vocab"], tie_embeddings=m["tie_embeddings"], dtype=dtype)


@pytest.fixture(scope="module")
def setup():
    w = R.make_weights(M_SMALL, 2 ** 33 + 5)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    ids = np.random.default_rng(0).integers(3, M_SMALL["vocab"], N + 1)
    return w, w32, [int(x) for x in ids]


def test_weights_come_from_the_seed():
    a = R.make_weights(M_SMALL, 7)
    b = R.make_weights(M_SMALL, 7)
    c = R.make_weights(M_SMALL, 8)
    assert all(bool(jnp.array_equal(x, y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    assert not bool(jnp.array_equal(a["blocks"]["p0"]["wq"],
                                    c["blocks"]["p0"]["wq"]))
    # the padding rows of the vocabulary are zero
    big = dict(M_SMALL, vocab=1100)
    tok = R.make_weights(big, 1)["embed"]["tok"]
    assert tok.shape[0] == 1280
    assert not bool(jnp.any(tok[1100:]))


def test_prefill_chunks_and_paged_decode_match_the_reference(setup):
    from repro.models import model as M
    _, w32, ids = setup
    cfg = program_cfg(M_SMALL, "float32")
    ref = R.logits_at(M_SMALL, w32, [ids], [N - 1], 2)[0]   # rows N-1, N
    tok = jnp.asarray(ids[:N], jnp.int32)[None]
    whole, _ = M.prefill(cfg, w32, {"tokens": tok}, impl="xla")
    pages = -(-(N + 1) // PAGE)
    tables = jnp.arange(1, pages + 1, dtype=jnp.int32)[None]
    pool = M.init_paged_cache(cfg, 1, N + 1, pages + 1, PAGE)
    lc, pool = M.prefill_chunk(cfg, w32, pool, tok[:, :32], jnp.int32(0),
                               tables, impl="xla")
    lc, pool = M.prefill_chunk(cfg, w32, pool, tok[:, 32:], jnp.int32(32),
                               tables, impl="xla")
    ld, _ = M.decode_step(cfg, w32, pool,
                          jnp.asarray([[ids[N]]], jnp.int32),
                          jnp.asarray([N], jnp.int32), block_tables=tables,
                          impl="xla")
    scale = np.abs(ref).max()
    for got, row in ((whole, 0), (lc, 0), (ld, 1)):
        err = np.abs(np.asarray(got[0, -1], np.float32) - ref[row]).max()
        assert err <= 1e-4 * scale, (row, err, scale)


def test_served_tokens_lie_at_the_reference_best(setup):
    from repro.serve.engine import Request, ServingEngine
    w, _, ids = setup
    cfg = program_cfg(M_SMALL, "bfloat16")
    eng = ServingEngine(cfg, w, max_slots=2, max_len=128, page_size=PAGE,
                        prefill_chunk=32)
    prompts = [ids[:20], ids[:N]]            # whole and chunked prefill
    done = eng.generate([Request(prompt=p, max_new_tokens=12, req_id=i)
                         for i, p in enumerate(prompts)])
    outs = [r.output for r in sorted(done, key=lambda r: r.req_id)]
    gaps = R.served_gaps(M_SMALL, w, prompts, outs)
    assert np.nanmax(gaps) <= 0.05
    bad = [o[:5] + [(o[5] + 1) % M_SMALL["vocab"]] + o[6:] for o in outs]
    assert np.nanmax(R.served_gaps(M_SMALL, w, prompts, bad)) > 0.05
