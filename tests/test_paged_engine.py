"""Differential + property tests for the paged serving engine (PR 8).

The paged KV cache (``serve/paging.py`` + the paged ``ServingEngine``)
must be *behaviourally invisible*: under greedy decoding, every request's
token stream must be bit-identical to the dense per-slot engine
(``page_size=0`` — the preserved reference), across seeded-random
schedules of admissions, chunked prefills, early EOS, waiting-queue
churn and pool-exhaustion evictions.  Chunked-prefill exactness is
asserted on the pure-global-attention arch (granite): attention is
position-masked so chunking cannot change the math; recurrent archs
(xlstm) get paged-vs-dense exactness WITHOUT chunking plus a
model-layer state-closeness check (chunked scans re-associate float
reductions, so bitwise equality is not a property there).

The ``BlockAllocator`` property suite drives random alloc/grow/free
traces and asserts the pool invariants after every op: no double-maps,
no leaks, bounded fragmentation, failed grows are no-ops, and the
allocator is reconstructible from its block tables alone.

Where `hypothesis` is available the randomized suites also run under it
(slow job); the seeded loops below are the deterministic property layer.
"""
import dataclasses
import random

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import BlockKind
from repro.kernels import ops
from repro.models import model as M
from repro.serve.engine import Request, ServingEngine
from repro.serve.paging import BlockAllocator, pages_for

try:
    import hypothesis  # noqa: F401
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

CFG = get_config("granite-3-2b").reduced()
XCFG = get_config("xlstm-350m").reduced()
MAX_LEN = 64
# prompt lengths draw from a palette so jit prefill retraces stay bounded
LEN_PALETTE = (2, 3, 5, 9, 12, 15, 19, 27, 40)


@pytest.fixture(scope="module")
def params():
    return M.init_model_params(CFG, jax.random.PRNGKey(0))


def _engine(params, **kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    return ServingEngine(CFG, params, **kw)


@pytest.fixture(scope="module")
def dense(params):
    return _engine(params, page_size=0)


@pytest.fixture(scope="module")
def paged(params):
    return _engine(params, page_size=16)


@pytest.fixture(scope="module")
def chunked(params):
    return _engine(params, page_size=16, prefill_chunk=8)


@pytest.fixture(scope="module")
def tight(params):
    # 3 pool pages for 2 slots: decode growth exhausts the pool
    return _engine(params, page_size=16, kv_pool_tokens=48)


def schedule(seed, n=5, long_bias=False):
    """Seeded request mix: random prompts/budgets off the length palette
    (``long_bias`` skews odd requests long, exercising chunked prefill)."""
    rng = random.Random(seed)
    out = []
    for i in range(n):
        palette = LEN_PALETTE[-3:] if long_bias and i % 2 else LEN_PALETTE
        length = rng.choice(palette)
        prompt = [rng.randrange(1, CFG.vocab) for _ in range(length)]
        out.append((prompt, rng.choice((3, 4, 6))))
    return out


def run(engine, sched):
    reqs = [Request(prompt=list(p), max_new_tokens=m, req_id=i)
            for i, (p, m) in enumerate(sched)]
    done = engine.generate(reqs)
    assert all(r.done for r in reqs)
    assert len(done) == len(reqs)
    assert engine.free_slots() == list(range(engine.max_slots))
    if engine.paged:
        engine.allocator.check_invariants()
        assert engine.allocator.n_free == engine.num_pages - 1, "page leak"
    return {r.req_id: list(r.output) for r in done}


# ----------------------------------------------------------------------
# token-exact differential schedules (the tentpole's acceptance bar)
# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(3))
def test_paged_matches_dense(dense, paged, seed):
    sched = schedule(seed)
    assert run(paged, sched) == run(dense, sched)


@pytest.mark.parametrize("seed", range(2))
def test_chunked_prefill_matches_dense(dense, chunked, seed):
    sched = schedule(100 + seed, long_bias=True)
    before = chunked.n_prefill_chunks
    assert run(chunked, sched) == run(dense, sched)
    assert chunked.n_prefill_chunks > before, "no prompt actually chunked"


def test_eviction_recompute_matches_dense(dense, tight):
    # 15-token prompts cross a page boundary mid-decode; with 3 pool
    # pages and 2 slots the growth must preempt and later re-prefill
    sched = [([k + 1] * 15, 6) for k in range(3)]
    before = tight.n_evictions
    assert run(tight, sched) == run(dense, sched)
    assert tight.n_evictions > before, "pool pressure never preempted"


def test_admit_step_surface(paged):
    """The seed's direct admit/step API still works on the paged engine."""
    r1 = Request(prompt=[1, 5, 9], max_new_tokens=3, req_id=0)
    r2 = Request(prompt=[1, 7], max_new_tokens=3, req_id=1)
    r3 = Request(prompt=[1, 2, 3], max_new_tokens=3, req_id=2)
    assert paged.admit(r1)
    assert paged.admit(r2)
    assert not paged.admit(r3)          # both slots busy
    for _ in range(64):
        paged.step()
        if r1.done and r2.done:
            break
    assert r1.done and r2.done
    assert paged.admit(r3)
    paged.generate([])                  # drain
    assert r3.done and len(r3.output) == 3


def test_submit_rejects_impossible_requests(tight):
    with pytest.raises(ValueError):     # 60-token footprint > 3 pages
        tight.submit(Request(prompt=[1] * 40, max_new_tokens=20, req_id=0))
    with pytest.raises(ValueError):     # prompt alone exceeds max_len
        tight.submit(Request(prompt=[1] * MAX_LEN, max_new_tokens=1,
                             req_id=1))
    assert not tight.waiting


def test_ttft_timestamps_and_stats(paged):
    r = Request(prompt=[2, 4, 6], max_new_tokens=3, req_id=0)
    paged.generate([r])
    assert r.t_submit is not None and r.t_first is not None
    assert r.t_first >= r.t_submit
    s = paged.stats()
    assert s["paged"] == 1 and s["page_size"] == 16
    assert s["pages_free"] == s["n_pages"]          # drained


# ----------------------------------------------------------------------
# recurrent arch: paged scheduling exact without chunking; chunked
# prefill validated at the model layer (state closeness, not bitwise)
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def xparams():
    return M.init_model_params(XCFG, jax.random.PRNGKey(1))


def test_paged_matches_dense_recurrent(xparams):
    sched = schedule(7, n=4)
    kw = dict(max_slots=2, max_len=MAX_LEN)
    want = run(ServingEngine(XCFG, xparams, page_size=0, **kw), sched)
    got = run(ServingEngine(XCFG, xparams, page_size=16, **kw), sched)
    assert got == want


def test_paged_matches_dense_mixed_pattern():
    """A period of global attention and a recurrent block, then a
    remainder global-attention layer: the period's pool rides the layer
    scan beside the recurrent state, the remainder's pool is a one-layer
    stack of its own."""
    cfg = dataclasses.replace(get_config("recurrentgemma-2b").reduced(),
                              pattern=(BlockKind.ATTN, BlockKind.RGLRU))
    flags = M.paged_leaf_flags(cfg, M.init_paged_cache(cfg, 2, 32, 5, 16))
    assert sum(flags) == 4          # blocks p0 and rem r0, K and V each
    mparams = M.init_model_params(cfg, jax.random.PRNGKey(2))
    sched = schedule(11, n=4)
    kw = dict(max_slots=2, max_len=MAX_LEN)
    want = run(ServingEngine(cfg, mparams, page_size=0, **kw), sched)
    got = run(ServingEngine(cfg, mparams, page_size=16, **kw), sched)
    assert got == want


def test_chunked_prefill_state_matches_full_recurrent(xparams):
    assert M.chunked_prefill_supported(XCFG)
    rng = random.Random(3)
    toks = [rng.randrange(1, XCFG.vocab) for _ in range(21)]
    arr = jnp.asarray(toks, jnp.int32)[None]
    logits_full, cache_full = M.prefill(XCFG, xparams, {"tokens": arr},
                                        cache_len=32)
    cache = M.init_cache(XCFG, 1, 32)
    bt = jnp.zeros((1, 2), jnp.int32)   # no attention leaves: table unused
    pos = 0
    logits = None
    while pos < len(toks):
        piece = arr[:, pos:pos + 8]
        logits, cache = M.prefill_chunk(XCFG, xparams, cache, piece,
                                        jnp.asarray(pos, jnp.int32), bt)
        pos += piece.shape[1]
    np.testing.assert_allclose(np.asarray(logits[0, -1]),
                               np.asarray(logits_full[0, -1]),
                               rtol=1e-4, atol=1e-4)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(cache)[0],
            jax.tree_util.tree_flatten_with_path(cache_full)[0]):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-4, atol=1e-4,
            err_msg=f"state leaf {jax.tree_util.keystr(path)}")


# ----------------------------------------------------------------------
# sampling keys: (seed, req_id, attempt, position) — the PRNG-reuse fix
# ----------------------------------------------------------------------
@pytest.fixture(scope="module")
def sampled(params):
    return _engine(params, page_size=16, greedy=False, sample_seed=7)


def test_sampled_stream_reproducible(sampled):
    sched = schedule(42, n=3)
    assert run(sampled, sched) == run(sampled, sched)


def test_redelivery_draws_fresh_randomness(sampled):
    """Regression: the seed keyed sampling on ``PRNGKey(req_id)`` alone,
    so an at-least-once redelivery replayed the lost attempt's stream."""
    def go(attempt):
        r = Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=8, req_id=9,
                    attempt=attempt)
        sampled.generate([r])
        return list(r.output)
    assert go(0) == go(0)               # same attempt: reproducible
    assert go(0) != go(1)               # new attempt: fresh draws


def test_sampling_key_varies_with_position(sampled):
    """Regression: a fixed per-request key draws the same index whenever
    the logits repeat; the position fold breaks that."""
    uniform = jnp.zeros((CFG.vocab,))
    req = Request(prompt=[1, 2], max_new_tokens=8, req_id=5)
    draws = []
    for _ in range(6):
        draws.append(sampled._sample_token(uniform, req))
        req.output.append(0)
    assert len(set(draws)) > 1


def test_sampling_key_varies_with_attempt(sampled):
    uniform = jnp.zeros((CFG.vocab,))
    draws = {sampled._sample_token(
        uniform, Request(prompt=[1], max_new_tokens=1, req_id=5,
                         attempt=a)) for a in range(6)}
    assert len(draws) > 1


# ----------------------------------------------------------------------
# paged attention kernels: Pallas (interpret) vs the jnp oracle
# ----------------------------------------------------------------------
# (KV heads, query heads per KV head, head dim): the first layout is the
# original case; then granite-3-2b's, qwen2.5-14b's and a multi-head one
HEAD_LAYOUTS = [(2, 2, 16), (8, 4, 64), (8, 5, 128), (4, 1, 32)]
PAGE, P = 8, 3


def _layout_id(layout):
    return "kv{}-g{}-hd{}".format(*layout)


def _paged_rows(rng, kv_lens):
    """Block tables for sequences of ``kv_lens`` keys (0 = an inactive row,
    whose table is all zeros) over a pool with every page id shuffled."""
    need = [-(-n // PAGE) for n in kv_lens]
    ids = list(1 + rng.permutation(sum(need)))
    bt = np.zeros((len(kv_lens), P), np.int32)
    for r, n in enumerate(need):
        bt[r, :n] = [ids.pop() for _ in range(n)]
    return bt, sum(need) + 1


def _pools(rng, layers, npages, layout):
    """K and V pools as the engine stores them, (layers, npages, PAGE,
    KV * hd): each layer's pages drawn on their own."""
    KV, _, hd = layout
    return [rng.standard_normal((layers, npages, PAGE, KV * hd), np.float32)
            for _ in range(2)]


def _decode_case(layout):
    """Rows: the original two, one key, a length ending on a page
    boundary, a full block table, an inactive row (kv_len 1 on the
    scratch page, as the engine passes it)."""
    KV, G, hd = layout
    rng = np.random.default_rng(0)
    kv_len = np.array([13, 20, 1, 16, P * PAGE, 1], np.int32)
    bt, npages = _paged_rows(rng, [13, 20, 1, 16, P * PAGE, 0])
    q = rng.standard_normal((len(kv_len), 1, KV * G, hd), np.float32)
    return rng, q, bt, kv_len, npages


@pytest.mark.parametrize("layout", HEAD_LAYOUTS, ids=_layout_id)
def test_paged_decode_kernel_interpret_matches_ref(layout):
    rng, q, bt, kv_len, npages = _decode_case(layout)
    kp, vp = _pools(rng, 1, npages, layout)
    ref_out = ops.paged_decode_attention(q, kp, vp, bt, kv_len, 0,
                                         impl="ref")
    int_out = ops.paged_decode_attention(q, kp, vp, bt, kv_len, 0,
                                         impl="interpret")
    np.testing.assert_allclose(np.asarray(int_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layer", range(3))
@pytest.mark.parametrize("layout", HEAD_LAYOUTS[:2], ids=_layout_id)
def test_paged_decode_kernel_reads_its_layer(layout, layer):
    """A three-layer stack read at ``layer`` matches the oracle given
    that layer's pages alone."""
    rng, q, bt, kv_len, npages = _decode_case(layout)
    kp, vp = _pools(rng, 3, npages, layout)
    ref_out = ops.paged_decode_attention(q, kp[layer][None],
                                         vp[layer][None], bt, kv_len, 0,
                                         impl="ref")
    int_out = ops.paged_decode_attention(q, kp, vp, bt, kv_len,
                                         jnp.int32(layer), impl="interpret")
    np.testing.assert_allclose(np.asarray(int_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


def _prefill_case(layout, C):
    """Chunks of C queries ending at the original two lengths, at the
    first key(s), on a page boundary, at a full block table, and an
    inactive row (a chunk at 0 on the scratch page)."""
    KV, G, hd = layout
    rng = np.random.default_rng(1)
    kv_len = np.array([13, 20, C, 16, P * PAGE, C], np.int32)
    bt, npages = _paged_rows(rng, [13, 20, C, 16, P * PAGE, 0])
    q = rng.standard_normal((len(kv_len), C, KV * G, hd), np.float32)
    return rng, q, bt, kv_len, kv_len - C, npages


@pytest.mark.parametrize("C", [5, 1])
@pytest.mark.parametrize("layout", HEAD_LAYOUTS, ids=_layout_id)
def test_paged_prefill_kernel_interpret_matches_ref(layout, C):
    rng, q, bt, kv_len, q_off, npages = _prefill_case(layout, C)
    kp, vp = _pools(rng, 1, npages, layout)
    ref_out = ops.paged_prefill_attention(q, kp, vp, bt, kv_len, q_off, 0,
                                          impl="ref")
    int_out = ops.paged_prefill_attention(q, kp, vp, bt, kv_len, q_off, 0,
                                          impl="interpret")
    np.testing.assert_allclose(np.asarray(int_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("layer", range(3))
@pytest.mark.parametrize("layout", HEAD_LAYOUTS[:2], ids=_layout_id)
def test_paged_prefill_kernel_reads_its_layer(layout, layer):
    """A three-layer stack read at ``layer`` matches the oracle given
    that layer's pages alone."""
    rng, q, bt, kv_len, q_off, npages = _prefill_case(layout, 5)
    kp, vp = _pools(rng, 3, npages, layout)
    ref_out = ops.paged_prefill_attention(q, kp[layer][None],
                                          vp[layer][None], bt, kv_len,
                                          q_off, 0, impl="ref")
    int_out = ops.paged_prefill_attention(q, kp, vp, bt, kv_len, q_off,
                                          jnp.int32(layer),
                                          impl="interpret")
    np.testing.assert_allclose(np.asarray(int_out), np.asarray(ref_out),
                               rtol=1e-4, atol=1e-5)


# ----------------------------------------------------------------------
# BlockAllocator property suite
# ----------------------------------------------------------------------
def _alloc_trace(rng, steps=300, num_pages=17, page_size=8, n_seqs=6):
    """Random alloc/grow/free trace, invariants checked after every op."""
    alloc = BlockAllocator(num_pages, page_size, reserved=(0,))
    capacity = num_pages - 1
    live = {}
    for _ in range(steps):
        sid = rng.randrange(n_seqs)
        if rng.random() < 0.65:
            want = live.get(sid, 0) + rng.randrange(1, 3 * page_size)
            snap = alloc.snapshot()
            if alloc.ensure(sid, want):
                live[sid] = max(live.get(sid, 0), want)
            else:
                assert alloc.snapshot() == snap, "failed grow mutated state"
        else:
            freed = alloc.free(sid)
            assert freed == pages_for(live.pop(sid, 0), page_size)
        alloc.check_invariants()
        mapped = sum(pages_for(v, page_size) for v in live.values())
        assert mapped <= capacity
        assert alloc.n_free == capacity - mapped
    for sid in list(live):
        alloc.free(sid)
        live.pop(sid)
        alloc.check_invariants()
    assert alloc.n_free == capacity and alloc.n_seqs == 0
    return alloc


@pytest.mark.parametrize("seed", range(8))
def test_allocator_random_traces(seed):
    _alloc_trace(random.Random(seed))


@pytest.mark.parametrize("seed", range(4))
def test_allocator_reconstructible_from_tables(seed):
    rng = random.Random(1000 + seed)
    alloc = BlockAllocator(17, 8, reserved=(0,))
    for sid in range(5):
        alloc.ensure(sid, rng.randrange(1, 30))
    tables, lens = alloc.snapshot()
    rebuilt = BlockAllocator.from_tables(17, 8, tables, lens, reserved=(0,))
    assert rebuilt.snapshot() == alloc.snapshot()
    assert sorted(rebuilt._free) == sorted(alloc._free)
    assert rebuilt.fragmentation() == alloc.fragmentation()


def test_from_tables_rejects_corruption():
    with pytest.raises(ValueError):     # double-mapped page
        BlockAllocator.from_tables(8, 4, {0: [1, 2], 1: [2]},
                                   {0: 8, 1: 4})
    with pytest.raises(ValueError):     # reserved page mapped
        BlockAllocator.from_tables(8, 4, {0: [0]}, {0: 4})
    with pytest.raises(ValueError):     # page outside the pool
        BlockAllocator.from_tables(8, 4, {0: [9]}, {0: 4})


def test_allocator_rejects_bad_config():
    with pytest.raises(ValueError):
        BlockAllocator(8, 0)
    with pytest.raises(ValueError):
        BlockAllocator(8, 4, reserved=(8,))


# ----------------------------------------------------------------------
# deep sweeps: the slow job's layer (hypothesis where available)
# ----------------------------------------------------------------------
@pytest.mark.slow
@pytest.mark.parametrize("seed", range(5, 13))
def test_paged_matches_dense_deep(dense, paged, chunked, seed):
    sched = schedule(seed, n=8, long_bias=True)
    want = run(dense, sched)
    assert run(paged, sched) == want
    assert run(chunked, sched) == want


@pytest.mark.slow
def test_eviction_during_chunked_prefill(dense, params):
    """Pool pressure preempting a slot that is mid-chunked-prefill: its
    partial pages free, and the re-prefill still matches dense."""
    eng = _engine(params, page_size=16, prefill_chunk=8,
                  kv_pool_tokens=64)
    # 27-token prompts fill both slots' pages at admission (2+2 of 4);
    # 8 new tokens push past 2 pages mid-decode, forcing a preemption
    # while the other slot can still be mid-chunked-prefill
    sched = [(list(range(1, 28)), 8), (list(range(2, 29)), 8),
             (list(range(3, 30)), 8)]
    want = run(dense, sched)
    assert run(eng, sched) == want
    assert eng.n_evictions > 0
    assert eng.n_prefill_chunks > 0


@pytest.mark.slow
@pytest.mark.skipif(not HAVE_HYPOTHESIS, reason="hypothesis not installed")
def test_allocator_traces_hypothesis():
    from hypothesis import given, settings, strategies as st

    @given(seed=st.integers(min_value=0, max_value=2**32 - 1),
           num_pages=st.integers(min_value=2, max_value=40),
           page_size=st.integers(min_value=1, max_value=32))
    @settings(max_examples=150, deadline=None)
    def check(seed, num_pages, page_size):
        _alloc_trace(random.Random(seed), steps=120, num_pages=num_pages,
                     page_size=page_size)

    check()
