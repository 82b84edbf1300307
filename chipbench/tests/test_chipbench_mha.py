"""The ``deepseek-7b-pp`` shape at a CPU size: full multi-head attention
(as many KV heads as query heads) at head dim 128, an untied head.

* the float32 reference against the program's whole prefill, chunked
  prefill through the paged pool and paged decode step, with the
  tolerance of ``test_chipbench_reference.py``;
* a whole CPU run of ``run.measure`` under a ``rag``-shaped mix (steady
  arrivals, every prompt longer than the chunk): ``correct``, and traced,
  ``prefill_wait_share`` between 0 and 100;
* the ``prefill_wait_share`` reader on a synthetic record."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import _paths  # noqa: F401
import harness
import run
from layer_metrics import prefill_wait_share
from references import dense_decoder as R
from test_chipbench_reference import PAGE, program_cfg

M_MHA = dict(n_layers=2, d_model=256, n_heads=4, n_kv_heads=4,
             head_dim=128, d_ff=512, vocab=1500, rope_theta=10000.0,
             tie_embeddings=False)
N = 70
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}


def test_prefill_chunks_and_paged_decode_match_the_reference():
    from repro.models import model as M
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32),
                       R.make_weights(M_MHA, 2 ** 33 + 7))
    assert "head" in w32["embed"]
    ids = [int(x) for x in np.random.default_rng(1).integers(
        3, M_MHA["vocab"], N + 1)]
    cfg = program_cfg(M_MHA, "float32")
    ref = R.logits_at(M_MHA, w32, [ids], [N - 1], 2)[0]   # rows N-1, N
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


def rag_spec():
    """A CPU cell of the ``deepseek-7b-pp.rag`` kind, under that cell's
    name so the run reads the metrics listed for it."""
    model = dict(M_MHA, vocab=512, dtype="bfloat16")
    return harness.Spec(
        name="deepseek-7b-pp.rag",
        entry={"name": "deepseek-7b-pp.rag", "config": "-", "traffic": "-",
               "chips": 1},
        cell={"rate_per_s": 3.0,
              "engine": {"slots": 2, "max_len": 256, "kv_pool_tokens": 512,
                         "page_size": 16, "prefill_chunk": 32},
              # the bf16 served-token bound of test_chipbench_reference.py
              "limits": {"max_logit_gap": 0.05}},
        config={"arch": "deepseek-7b-smoke", "reference": "dense_decoder",
                "model": model},
        mix={"arrivals": {"process": "gamma", "cv": 1.0, "pattern_seed": 5},
             "prompt_tokens": {"dist": "lognormal", "median": 80,
                               "sigma": 0.5, "min": 33, "max": 200},
             "output_tokens": {"dist": "fixed", "value": 8}})


def test_traced_rag_run_is_correct_and_reads_prefill_wait_share():
    spec = rag_spec()
    sched = harness.schedule(spec, 2 ** 32 + 11, 2.0)
    assert min(len(d.prompt) for d in sched) > \
        spec.engine["prefill_chunk"]
    line, notes = run.measure(spec, 2 ** 32 + 11, 2.0, True,
                              jax.devices()[0], PEAKS, time.monotonic())
    assert line["correct"], notes
    assert line["failed"] == 0 and line["attempted"] == len(sched)
    got = line["metrics"]
    assert 0 < got["prefill_wait_share"]["value"] < 100, got
    assert got["prefill_wait_share"]["unit"] == "%"


def _req(due, r_end, ok=True):
    return {"due": due, "r_end": r_end, "ok": ok}


def _span(name, t0, t1):
    return {"name": name, "t_start": t0, "t_end": t1}


def test_prefill_wait_share_reader():
    record = {"requests": [_req(0.0, 4.0), _req(1.0, 5.0),
                           _req(2.0, None, ok=False)],
              "spans": [_span("first_token", 0.5, 1.5),
                        _span("first_token", 2.0, 3.0),
                        _span("prefill", 0.5, 1.0),
                        {"name": "first_token", "t_start": 4.0,
                         "t_end": None}]}
    assert prefill_wait_share.read(record) == pytest.approx(25.0)
    # the parent program writes no such span: silent, never 0
    record["spans"] = record["spans"][2:]
    assert prefill_wait_share.read(record) is None
    assert prefill_wait_share.read({"requests": [], "spans": []}) is None
    assert prefill_wait_share.read({}) is None
